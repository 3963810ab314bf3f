"""Paged prefix-shared KV cache + speculative decoding + multi-replica
routing (PR-12 tentpole).

The load-bearing invariants:

1. **Parity** — block-table decode produces the same logits as the
   full batch forward at fp32 tolerance; prefix-shared admissions see
   bit-identical prefill logits.
2. **Bit-identity** — speculative greedy decode emits exactly the same
   token streams as non-speculative greedy decode (the acceptance-rule
   guarantee), whatever the n-gram drafter proposes.
3. **Safety** — pool exhaustion rejects admission and never corrupts a
   live slot; copy-on-write forks before the first divergent write;
   refcounts return blocks on evict (with LRU retention for prefix
   blocks).
4. **Static shapes** — the paged serve (decode, batched chunk prefill,
   verify, block copy) runs under ``fail_on_recompile`` with zero
   post-warmup retraces.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (InferenceEngine, NGramDrafter,
                                     PagedKVCacheSpec, PoolExhausted,
                                     ReplicaRouter,
                                     shared_prefix_requests,
                                     synthetic_requests)
from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_apply, gpt2_init
from deepspeed_tpu.monitor.serving import ServingAggregator
from deepspeed_tpu.parallel.topology import build_mesh

CFG32 = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32)
CFG = GPT2_CONFIGS["gpt2-tiny"]


@pytest.fixture(scope="module")
def params32():
    return gpt2_init(jax.random.PRNGKey(0), CFG32)


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.PRNGKey(1), CFG)


def _prompt(n, seed=0, vocab=None):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab or CFG32.vocab_size,
                        size=n).astype(np.int32)


def _engine(params, *, slots=8, max_len=64, chunk=8,
            block_size=16, num_blocks=0, spec_k=0, cfg=CFG32, **tel):
    config = {"inference": {"max_slots": slots, "max_seq_len": max_len,
                            "prefill_chunk": chunk,
                            "block_size": block_size,
                            "num_blocks": num_blocks,
                            "spec_k": spec_k}}
    config.update(tel)
    return InferenceEngine(cfg, params, config=config)


# --------------------------------------------------------------------- #
# Paged primitives (device units)
# --------------------------------------------------------------------- #
class TestPagedPrimitives:
    def test_positions_to_blocks_resolves_and_deadens(self):
        bt = jnp.asarray([[3, 7, kv_cache.DEAD_BLOCK]], jnp.int32)
        pos = jnp.asarray([[0, 5, 9, 11, 13]], jnp.int32)   # bs=4, J=3
        bt_rows = jnp.broadcast_to(bt[:, None, :], (1, 5, 3))
        blk, off = kv_cache.positions_to_blocks(bt_rows[0], pos[0], 4)
        assert blk.tolist() == [3, 7, kv_cache.DEAD_BLOCK,
                                kv_cache.DEAD_BLOCK, kv_cache.DEAD_BLOCK]
        assert off.tolist() == [0, 1, 1, 3, 1]
        # Past the table entirely (pos // bs >= J) is dead too.
        blk2, _ = kv_cache.positions_to_blocks(
            jnp.asarray([5, 6, 7], jnp.int32), jnp.int32(13), 4)
        assert int(blk2) == kv_cache.DEAD_BLOCK

    def test_paged_write_rows_lands_and_dead_rows_dont(self):
        pool = jnp.zeros((1, 2, 4, 2, 4, 3), jnp.float32)  # [L,G,B,nH,bs,D]
        new = jnp.ones((2, 2, 2, 3), jnp.float32) * \
            jnp.asarray([1.0, 2.0])[None, :, None, None]
        blk = jnp.asarray([[1, kv_cache.DEAD_BLOCK], [3, 0]], jnp.int32)
        off = jnp.asarray([[2, 0], [0, 3]], jnp.int32)
        out_k, out_v = kv_cache.paged_write_rows(pool, pool, new, 2 * new,
                                                 0, blk, off)
        np.testing.assert_array_equal(np.asarray(out_v),
                                      2 * np.asarray(out_k))
        out = np.array(out_k)[0]
        assert (out[0, 1, :, 2] == 1.0).all()       # row 0 of group 0
        assert (out[1, 3, :, 0] == 1.0).all()       # row 0 of group 1
        assert (out[1, 0, :, 3] == 2.0).all()       # row 1 of group 1
        # Dead row wrote nowhere; everything else untouched.
        out[0, 1, :, 2] = 0
        out[1, 3, :, 0] = 0
        out[1, 0, :, 3] = 0
        assert (out == 0).all()

    @pytest.mark.parametrize("groups", [1, 2, "dp2"])
    @pytest.mark.parametrize("layout", ["gpt2", "latent", "window_full",
                                        "state"])
    def test_copy_pages_equals_a_numpy_copy(self, layout, groups):
        """The one device copy of every pool (a copy-on-write fork, a
        snapshot into or out of a stream's page) over the four pool
        layouts the cells hold — GPT-2's ``head_dim`` 64 folded into the
        lanes, the latent row, the window + full pair (a pool a class,
        block ids of its own), the fp32 state page and its normaliser —
        with one group, two, and two over a dp mesh (every shard copies
        within its own groups); the last group has nothing to copy."""
        bf16, f32 = jnp.bfloat16, jnp.float32
        pools = {   # name -> (blocks a group, one block's tile, dtype)
            "gpt2": {"k": (5, (4, 8, 128), bf16), "v": (5, (4, 8, 128), bf16)},
            "latent": {"latent": (6, (1, 16, 576), bf16)},
            "window_full": {"k.full": (7, (2, 16, 128), bf16),
                            "k.window": (4, (2, 16, 128), bf16)},
            "state": {"state": (5, (2, 144, 16), f32),
                      "norm": (5, (2, 16, 16), f32)}}[layout]
        G = 1 if groups == 1 else 2
        mesh = build_mesh(devices=jax.devices()[:2]) if groups == "dp2" \
            else None
        rng = np.random.default_rng(7)
        for name, (B, tile, dtype) in pools.items():
            before = rng.standard_normal((3, G, B) + tile).astype(np.float32)
            pool = jnp.asarray(before, dtype)
            before = np.asarray(pool.astype(f32))
            src = np.array([1, B - 1][:G], np.int32)
            dst = np.array([B - 2, -1][:G], np.int32)
            if G == 1:
                dst[:] = B - 2
            if mesh is not None:
                pool = jax.device_put(pool, kv_cache.paged_shardings(
                    mesh, [name])[name])
            out = jax.jit(lambda p, s, d: kv_cache.copy_pages(
                p, s, d, mesh))(pool, src, dst)
            assert out.dtype == dtype and out.shape == pool.shape
            want = before.copy()
            want[:, 0, B - 2] = before[:, 0, 1]
            np.testing.assert_array_equal(
                np.asarray(out.astype(f32)), want, err_msg=name)

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="divide"):
            PagedKVCacheSpec(num_layers=1, num_slots=4, num_blocks=8,
                             block_size=3, max_len=8, num_heads=2,
                             head_dim=4).validate()
        with pytest.raises(ValueError, match="divisible"):
            PagedKVCacheSpec(num_layers=1, num_slots=4, num_blocks=7,
                             block_size=2, max_len=8, num_heads=2,
                             head_dim=4, num_groups=2).validate()


# --------------------------------------------------------------------- #
# The in-place write (interpret mode here; tests/test_tpu_compile.py holds
# what the chip compiler makes of it) against a plain NumPy loop
# --------------------------------------------------------------------- #
def _write_case(kind, D, *, G=1, nH=2, seed=0):
    """(logical K pool, logical V pool, k rows, v rows, block tables
    [G, Sg, J], positions [G, Sg, K]) for one of the four shapes the
    serving programs write: R = slots (decode, K=1), slots x 5 (verify),
    a 128-row prefill chunk, a whole padded prompt. Tables hold dead
    slots, unallocated tails and positions past the table."""
    bs, L = 16, 2
    Sg, K, J = {"decode": (6, 1, 4), "verify": (4, 5, 4),
                "chunk": (1, 128, 12), "whole_prompt": (1, 256, 16)}[kind]
    B = Sg * J + 3
    rng = np.random.default_rng(seed)
    pool_k = rng.standard_normal((L, G, B, nH, bs, D)).astype(np.float32)
    pool_v = rng.standard_normal((L, G, B, nH, bs, D)).astype(np.float32)
    bt = np.full((G, Sg, J), kv_cache.DEAD_BLOCK, np.int32)
    pos = np.zeros((G, Sg, K), np.int32)
    for g in range(G):
        free = list(rng.permutation(B))
        for s in range(Sg):
            if kind in ("decode", "verify") and s == 1:
                continue                        # an inactive slot
            if kind == "whole_prompt" and g == G - 1 and G > 1:
                continue                        # not this group's slot
            if kind in ("decode", "verify"):
                # last stream sits at the table's end: verify's tail
                # rows fall past it and must write nowhere
                start = J * bs - 2 if s == Sg - 1 else \
                    int(rng.integers(0, (J - 1) * bs))
            else:
                start = 32 if kind == "chunk" else 0
            last = min(start + K - 1, J * bs - 1)
            # a whole prompt is padded to the table; only the prompt's
            # own blocks are allocated, the padding rows are dead
            if kind == "whole_prompt":
                last = 150
            for j in range(last // bs + 1):
                bt[g, s, j] = free.pop()
            pos[g, s] = start + np.arange(K)
    rows_k = rng.standard_normal((G, Sg * K, nH, D)).astype(np.float32)
    rows_v = rng.standard_normal((G, Sg * K, nH, D)).astype(np.float32)
    return pool_k, pool_v, rows_k, rows_v, bt, pos


def _numpy_write(pool, rows, layer, bt, pos, bs, live=None, ring=False):
    """The plain reference: one row at a time (``live`` [G, Sg, K]: the
    rows that are traffic, all by default; ``ring``: a bounded class's
    table, logical block j at slot ``j % J``)."""
    out = pool.copy()
    G, Sg, K = pos.shape
    J = bt.shape[-1]
    for g in range(G):
        for s in range(Sg):
            for k in range(K):
                j, off = divmod(int(pos[g, s, k]), bs)
                if ring:
                    j %= J
                if live is not None and not live[g, s, k]:
                    continue
                if j >= J or bt[g, s, j] == kv_cache.DEAD_BLOCK:
                    continue
                out[layer, g, bt[g, s, j], :, off, :] = rows[g, s * K + k]
    return out


def _device_write(pool_k, pool_v, rows_k, rows_v, layer, bt, pos, bs,
                  dtype=jnp.float32, mesh=None):
    from deepspeed_tpu.inference.served import write_targets
    D = pool_k.shape[-1]

    def step(kc, vc, rk, rv, bt, pos):
        blk, off = write_targets(bt, pos, bs)
        return kv_cache.paged_write_rows(kc, vc, rk, rv, layer, blk, off,
                                         mesh=mesh)

    held = lambda a: kv_cache.paged_folded_view(jnp.asarray(a, dtype))
    kc, vc = jax.jit(step, donate_argnums=(0, 1))(
        held(pool_k), held(pool_v), jnp.asarray(rows_k),
        jnp.asarray(rows_v), jnp.asarray(bt), jnp.asarray(pos))
    return (np.asarray(kv_cache.paged_logical_view(kc, D), np.float32),
            np.asarray(kv_cache.paged_logical_view(vc, D), np.float32))


# The run write's cases: pages of ``_BS`` rows, ``J`` table slots a stream;
# a stream's ``K`` rows start at ``starts[s]`` and its first ``lives[s]`` are
# live (its table holds exactly the pages those reach).
_BS = 64
_CHUNK = dict(K=512, J=12, lives=[512])
_RUN_CASES = {
    "a_row_a_stream": dict(K=1, J=4, starts=[5, 70, 0, 130, 200, 255],
                           lives=[1, 1, 0, 1, 1, 1], rows_a_run=1.0),
    "a_block_in_a_page": dict(K=4, J=4, starts=[0, 60, 128, 12, 200],
                              lives=[4, 4, 0, 4, 4], one_block=True,
                              rows_a_run=4.0),
    "a_block_in_a_page_unsaid": dict(K=4, J=4, starts=[0, 60, 128, 12, 200],
                                     lives=[4, 4, 0, 4, 2], rows_a_run=3.5),
    "a_block_astride_two_pages": dict(K=4, J=4, starts=[62, 126, 0, 190],
                                      lives=[4, 4, 0, 3], rows_a_run=11 / 6),
    "chunk_from_a_boundary": dict(_CHUNK, starts=[128], rows_a_run=64.0),
    "chunk_from_mid_page": dict(_CHUNK, starts=[72], rows_a_run=512 / 9),
    "chunk_from_an_odd_row": dict(_CHUNK, starts=[69], rows_a_run=512 / 9),
    "chunk_cut_by_last_idx": dict(_CHUNK, starts=[64], lives=[301],
                                  rows_a_run=301 / 5),
    "chunk_past_the_table": dict(_CHUNK, starts=[583], rows_a_run=185 / 3),
    "chunk_in_parts": dict(_CHUNK, starts=[72], rows_a_run=512 / 9),
    "ring_wraps": dict(K=128, J=4, starts=[222], lives=[128], ring=True,
                       rows_a_run=128 / 3),
    "two_groups_two_head_shards": dict(K=4, J=4, starts=[0, 62, 128, 12],
                                       lives=[4, 4, 0, 3], mesh=True,
                                       rows_a_run=11 / 4),
    "a_chunk_two_groups_two_head_shards": dict(
        _CHUNK, K=128, starts=[72], lives=[100], mesh=True,
        rows_a_run=100 / 2),
}
_RUN_PARAMS = [
    pytest.param(case, D, dtype, id=f"{case}-{D}-{jnp.dtype(dtype).name}")
    for case, c in sorted(_RUN_CASES.items())
    for D, dtype in ([(64, jnp.float32)] if c.get("mesh") else
                     [(64, jnp.bfloat16), (128, jnp.bfloat16)]
                     + ([(64, jnp.float32), (128, jnp.float32)]
                        if case in ("a_block_astride_two_pages",
                                    "chunk_from_an_odd_row", "ring_wraps")
                        else []))]


def _run_case(c, D, seed=0):
    """(logical pools, rows, tables [G, Sg, J], positions and ``live``
    [G, Sg, K]) of one of ``_RUN_CASES``."""
    G, nH = (2, 4) if c.get("mesh") else (1, 2)
    K, J, starts, lives = c["K"], c["J"], c["starts"], c["lives"]
    Sg, L = len(starts), 2
    B = Sg * J + 2
    rng = np.random.default_rng(seed)
    pk, pv = (rng.standard_normal((L, G, B, nH, _BS, D)).astype(np.float32)
              for _ in range(2))
    bt = np.full((G, Sg, J), kv_cache.DEAD_BLOCK, np.int32)
    pos = np.zeros((G, Sg, K), np.int32)
    live = np.zeros((G, Sg, K), bool)
    for g in range(G):
        free = list(rng.permutation(B))
        for s, (start, n) in enumerate(zip(starts, lives)):
            pos[g, s] = start + np.arange(K)
            live[g, s, :n] = True
            for j in range(start // _BS, (start + n - 1) // _BS + 1
                           if n else 0):
                if c.get("ring") or j < J:
                    bt[g, s, j % J] = free.pop()
    rk, rv = (rng.standard_normal((G, Sg * K, nH, D)).astype(np.float32)
              for _ in range(2))
    return pk, pv, rk, rv, bt, pos, live


def _run_step(c, D, dtype, mesh, by_row=False):
    """The traced write of a case: one call a run a step, or (``by_row``) a
    loop of calls of ONE row each."""
    ring = c.get("ring", False)

    def step(kc, vc, rk, rv, bt, pos, live):
        G, Sg, K = pos.shape
        table = jnp.broadcast_to(bt[:, :, None, :], pos.shape + bt.shape[-1:])
        blk, off = kv_cache.positions_to_blocks(table, pos, _BS, ring=ring)
        blk = jnp.where(live, blk, kv_cache.DEAD_BLOCK).reshape(G, Sg * K)
        off = off.reshape(G, Sg * K)
        if not by_row:
            return kv_cache.paged_write_rows(
                kc, vc, rk, rv, 1, blk, off, mesh=mesh, stream_rows=K,
                one_block=c.get("one_block", False))

        def one(i, pools):
            at = lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1, axis=1)
            return kv_cache.paged_write_rows(
                *pools, at(rk), at(rv), 1, at(blk), at(off), mesh=mesh)
        return jax.lax.fori_loop(0, Sg * K, one, (kc, vc))
    return step


def _run_write(c, pk, pv, rk, rv, bt, pos, live, dtype, mesh):
    """([K pool, V pool] a run a step, the same a row a call), logical
    fp32."""
    D = pk.shape[-1]
    held = lambda a: kv_cache.paged_folded_view(jnp.asarray(a, dtype))
    if mesh is not None:
        sh = kv_cache.paged_shardings(mesh)
        held = lambda a: jax.device_put(
            kv_cache.paged_folded_view(jnp.asarray(a, dtype)), sh["k"])
    out = []
    for by_row in (False, True):
        pools = jax.jit(_run_step(c, D, dtype, mesh, by_row))(
            held(pk), held(pv), *map(jnp.asarray, (rk, rv, bt, pos, live)))
        out.append([np.asarray(kv_cache.paged_logical_view(p, D), np.float32)
                    for p in pools])
    return out


def _in_parts(monkeypatch, request):
    """A step's VMEM holds 32 of these cases' rows: a chunk goes in parts.
    (The run write is traced once a shape: forget the traces made under the
    other limit, before and after.)"""
    from deepspeed_tpu.ops import paged_attention as pa
    monkeypatch.setattr(pa, "_WRITE_ROWS_BYTES", 2 ** 15)
    pa._write_runs_local.clear_cache()
    request.addfinalizer(pa._write_runs_local.clear_cache)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


class TestInPlaceWrite:
    @pytest.mark.parametrize("head_dim", [64, 128])
    @pytest.mark.parametrize("kind", ["decode", "verify", "chunk",
                                      "whole_prompt"])
    def test_write_matches_a_numpy_loop_over_rows(self, kind, head_dim):
        """Every written row lands with the same bits at (layer, block,
        :, offset, :) and every other cell of BOTH pools — the other
        layer, dead slots' blocks, blocks past a table — is
        bit-identical to what it was. head_dim 64 is held folded (two
        positions a lane row), 128 is not."""
        pk, pv, rk, rv, bt, pos = _write_case(kind, head_dim)
        assert kv_cache.kv_fold(head_dim, 16) == 128 // head_dim
        got_k, got_v = _device_write(pk, pv, rk, rv, 1, bt, pos, 16)
        np.testing.assert_array_equal(
            got_k, _numpy_write(pk, rk, 1, bt, pos, 16))
        np.testing.assert_array_equal(
            got_v, _numpy_write(pv, rv, 1, bt, pos, 16))
        assert not np.array_equal(got_k, pk)        # something landed

    @pytest.mark.parametrize("head_dim", [64, 128])
    def test_bf16_rows_land_with_their_own_bits(self, head_dim):
        """The serving pool is bf16: a written row is the bf16 rounding
        of the new row, nothing more (the kernel selects in fp32, which
        holds every bf16 exactly)."""
        pk, pv, rk, rv, bt, pos = _write_case("verify", head_dim, seed=3)
        rnd = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16),
                                   np.float32)
        got_k, got_v = _device_write(pk, pv, rk, rv, 0, bt, pos, 16,
                                     dtype=jnp.bfloat16)
        np.testing.assert_array_equal(
            got_k, _numpy_write(rnd(pk), rnd(rk), 0, bt, pos, 16))
        np.testing.assert_array_equal(
            got_v, _numpy_write(rnd(pv), rnd(rv), 0, bt, pos, 16))

    @pytest.mark.parametrize("head_dim,block_size,fold", [
        (64, 16, 2), (128, 16, 1), (32, 16, 4), (16, 4, 4), (80, 16, 1),
        (256, 8, 1)])
    def test_logical_view_round_trips(self, head_dim, block_size, fold):
        """``PagedKVCacheSpec.shape`` is the logical shape with ``fold``
        positions side by side in the lanes; the views are reshapes of
        the same bytes, both ways."""
        spec = PagedKVCacheSpec(num_layers=2, num_slots=2, num_blocks=6,
                                block_size=block_size,
                                max_len=2 * block_size, num_heads=3,
                                head_dim=head_dim, dtype=jnp.float32)
        assert spec.fold == fold == kv_cache.kv_fold(head_dim, block_size)
        assert spec.shape == (2, 1, 6, 3, block_size // fold,
                              fold * head_dim)
        assert spec.nbytes() == 2 * 4 * int(np.prod(spec.logical_shape))
        logical = jnp.arange(np.prod(spec.logical_shape),
                             dtype=jnp.float32).reshape(spec.logical_shape)
        held = kv_cache.paged_folded_view(logical)
        assert held.shape == spec.shape
        np.testing.assert_array_equal(
            np.asarray(held).ravel(), np.asarray(logical).ravel())
        np.testing.assert_array_equal(
            np.asarray(kv_cache.paged_logical_view(held, head_dim)),
            np.asarray(logical))
        np.testing.assert_array_equal(
            np.asarray(kv_cache.paged_layer_view(held, 1, head_dim)),
            np.asarray(logical)[1])
        # position t of a block: row t // fold, lanes (t % fold) * D ..
        t = block_size - 1
        np.testing.assert_array_equal(
            np.asarray(held)[1, 0, 4, 2, t // fold,
                             (t % fold) * head_dim:][:head_dim],
            np.asarray(logical)[1, 0, 4, 2, t])

    @pytest.mark.parametrize("head_dim", [64, 128])
    def test_dead_rows_and_positions_past_the_table_write_nowhere(
            self, head_dim):
        """All-dead tables (an inactive prefill group, an empty batch)
        and positions past the table leave the pools bit-identical."""
        pk, pv, rk, rv, bt, pos = _write_case("verify", head_dim, seed=5)
        dead = np.full_like(bt, kv_cache.DEAD_BLOCK)
        got_k, got_v = _device_write(pk, pv, rk, rv, 1, dead, pos, 16)
        np.testing.assert_array_equal(got_k, pk)
        np.testing.assert_array_equal(got_v, pv)
        past = pos + bt.shape[-1] * 16          # every position past it
        got_k, got_v = _device_write(pk, pv, rk, rv, 1, bt, past, 16)
        np.testing.assert_array_equal(got_k, pk)
        np.testing.assert_array_equal(got_v, pv)

    @pytest.mark.parametrize("kind", ["decode", "verify", "whole_prompt"])
    def test_two_groups_and_two_head_shards_on_a_mesh(self, kind):
        """dp=2 groups x mp=2 heads on four host devices: the write runs
        per shard (shard_map, group-local block ids) and the sharded
        pools hold what the NumPy loop holds."""
        from jax.sharding import NamedSharding
        from deepspeed_tpu.parallel.topology import build_mesh
        mesh = build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
        pk, pv, rk, rv, bt, pos = _write_case(kind, 64, G=2, nH=4, seed=7)
        from deepspeed_tpu.inference.served import write_targets

        def step(kc, vc, rk, rv, bt, pos):
            blk, off = write_targets(bt, pos, 16)
            return kv_cache.paged_write_rows(kc, vc, rk, rv, 1, blk, off,
                                             mesh=mesh)

        sh = kv_cache.paged_shardings(mesh)
        put = lambda a: jax.device_put(
            kv_cache.paged_folded_view(jnp.asarray(a)), sh["k"])
        kc, vc = jax.jit(step, donate_argnums=(0, 1),
                         out_shardings=(sh["k"], sh["v"]))(
            put(pk), put(pv), jnp.asarray(rk), jnp.asarray(rv),
            jnp.asarray(bt), jnp.asarray(pos))
        assert kc.sharding.is_equivalent_to(sh["k"], kc.ndim)
        np.testing.assert_array_equal(
            np.asarray(kv_cache.paged_logical_view(kc, 64)),
            _numpy_write(pk, rk, 1, bt, pos, 16))
        np.testing.assert_array_equal(
            np.asarray(kv_cache.paged_logical_view(vc, 64)),
            _numpy_write(pv, rv, 1, bt, pos, 16))

    @pytest.mark.parametrize("case,head_dim,dtype", _RUN_PARAMS)
    def test_a_run_a_step_leaves_what_a_row_a_step_leaves(
            self, case, head_dim, dtype, monkeypatch, request):
        """The write with a RUN of a stream's rows a grid step (``K`` > 1:
        ``stream_rows``, ``one_block``) against the NumPy loop over rows AND
        against the same rows written one a call, BYTE for byte, both pools
        whole: a row a stream; a block of four in a page, said and unsaid,
        and astride two; a 512-row chunk from a page's start (whole pages:
        the one store), from mid-page at a sublane-aligned row and at an odd
        one (a copy-on-write fork's start), cut by ``last_idx``, running
        past its table, written in parts (a stream too long for the step's
        VMEM); a ring class whose rows wrap into its first slot — pages of 64
        rows at head_dim 128 and folded (two positions a lane row, the
        select's 16-row groups) at 64, bf16 and fp32, and two groups over
        two head shards on a mesh."""
        from deepspeed_tpu.ops import paged_attention as pa
        c = _RUN_CASES[case]
        if case == "chunk_in_parts":
            _in_parts(monkeypatch, request)
        mesh = None
        if c.get("mesh"):
            mesh = build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
        pk, pv, rk, rv, bt, pos, live = _run_case(c, head_dim)
        got, by_row = _run_write(c, pk, pv, rk, rv, bt, pos, live, dtype,
                                 mesh)
        rnd = lambda a: np.asarray(jnp.asarray(a, dtype), np.float32)
        want = [_numpy_write(rnd(pool), rnd(rows), 1, bt, pos, _BS,
                             live=live, ring=c.get("ring", False))
                for pool, rows in ((pk, rk), (pv, rv))]
        for a, b, w, before in zip(got, by_row, want, (pk, pv)):
            bits = lambda x: x.view(np.uint32)
            np.testing.assert_array_equal(bits(a), bits(w))
            np.testing.assert_array_equal(bits(b), bits(w))
            assert not np.array_equal(w, rnd(before))   # something landed

    @pytest.mark.parametrize("case", sorted(_RUN_CASES))
    def test_write_step_counts_are_the_grids(self, case, monkeypatch,
                                             request):
        """``write_step_counts`` — host integers from each stream's first
        position and live rows — against the pools (rows landed, and runs: the
        (stream, page) pairs the NumPy loop touches) and against the grid the
        kernel is BUILT with (the traced ``pallas_call``'s)."""
        from deepspeed_tpu.ops import paged_attention as pa
        c = _RUN_CASES[case]
        if case == "chunk_in_parts":
            _in_parts(monkeypatch, request)
        pk, pv, rk, rv, bt, pos, live = _run_case(c, 128)
        G, Sg, K = pos.shape
        J = bt.shape[-1]
        landed = live & ((pos < J * _BS) | c.get("ring", False))
        rows, runs, steps = pa.write_step_counts(
            pos[..., 0], landed.sum(-1), K=K, block_size=_BS,
            one_block=c.get("one_block", False), num_heads=pk.shape[3],
            head_dim=128)
        assert rows == int(landed.sum())
        assert runs == len({(g, s, int(p) // _BS)
                            for (g, s, k), p in np.ndenumerate(pos)
                            if landed[g, s, k]})
        step = _run_step(c, 128, jnp.bfloat16, None)
        held = lambda a: kv_cache.paged_folded_view(
            jnp.asarray(a, jnp.bfloat16))
        jaxpr = jax.make_jaxpr(step)(held(pk), held(pv), rk, rv, bt, pos,
                                     live)
        grids = [eqn.params["grid_mapping"].grid for eqn in _eqns(jaxpr.jaxpr)
                 if eqn.primitive.name == "pallas_call"]
        assert grids == [(G, steps // G)]
        assert rows / runs == c["rows_a_run"]

    @pytest.mark.parametrize("program", ["decode_step", "prefill_step"])
    def test_pool_buffers_are_donated_and_reused(self, params32, program):
        """The engine's step hands back the SAME device buffers it was
        given: the compiled program aliases both pools to its outputs,
        and (where the platform reports it) the buffer address does not
        move across an execution."""
        eng = _engine(params32, slots=8, chunk=8)
        tok, _ = eng.prefill(_prompt(11), slot=0)
        eng.activate_slot(0, 11, tok)
        G, J = eng.dp, eng.cache_spec.max_blocks_per_slot
        where = lambda: [s.data.unsafe_buffer_pointer() for n in "kv"
                         for s in eng.cache[n].addressable_shards]
        before = where()
        if program == "decode_step":
            eng.decode_once()
            fn, args = eng._decode_fn, (
                eng._params, eng.cache["k"], eng.cache["v"],
                eng._no_fetch, jnp.zeros(8, jnp.int32),
                jnp.ones(8, bool), jnp.zeros(8, jnp.int32),
                jnp.asarray(eng.block_tables), eng._next_key(),
                jnp.float32(0))
        else:
            eng.prefill(_prompt(9, seed=1), slot=1)
            fn, args = eng._prefill_fn, (
                eng._params, eng.cache["k"], eng.cache["v"],
                jnp.zeros((G, 8), jnp.int32), jnp.zeros((G, J), jnp.int32),
                jnp.zeros(G, jnp.int32), jnp.zeros(G, jnp.int32),
                jnp.ones(G, jnp.int32), jnp.int32(1), eng._next_key(),
                jnp.float32(0))
        after = where()
        if not hasattr(fn, "lower"):        # the recompile sentinel's wrap
            fn = fn.__wrapped__
        compiled = fn.lower(*args).compile()
        from deepspeed_tpu.analysis.hlo_text import (
            input_output_alias_params)
        n = len(jax.tree_util.tree_leaves(eng._params))     # pools follow
        assert sorted(input_output_alias_params(compiled.as_text())) == \
            [n, n + 1]
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            eng.cache_spec.nbytes() // eng.dp           # per device
        assert after == before


# --------------------------------------------------------------------- #
# Host allocator: refcounts, prefix cache, CoW, exhaustion
# --------------------------------------------------------------------- #
class TestBlockAllocator:
    SPEC = PagedKVCacheSpec(num_layers=1, num_slots=4, num_blocks=8,
                            block_size=4, max_len=16, num_heads=2,
                            head_dim=4, num_groups=1, dtype=jnp.float32)

    def test_share_then_refcount_return_on_release(self):
        alloc = kv_cache.BlockAllocator(self.SPEC)
        prompt = _prompt(9, seed=1)                 # 2 full blocks + 1
        a = alloc.admit_prompt(0, 0, prompt, max_new=2)
        assert len(a.table) == 3 and a.matched == 0
        b = alloc.admit_prompt(1, 0, prompt, max_new=2)
        assert b.table[:2] == a.table[:2], "full blocks shared"
        assert b.table[2] != a.table[2], "partial block private"
        assert b.matched == 8 and b.cow_src is None
        assert alloc.blocks_in_use() == 4
        alloc.release(1, b.table)
        # Shared refs dropped; a's blocks still live.
        assert alloc.blocks_in_use() == 3
        alloc.release(0, a.table)
        assert alloc.blocks_in_use() == 0
        # Prefix blocks are LRU-retained (still matchable), private
        # partial block went back to the free list.
        assert alloc.available(0) == 8
        assert len(alloc.match_prefix(0, prompt)[0]) == 2

    def test_exact_match_forks_copy_on_write(self):
        alloc = kv_cache.BlockAllocator(self.SPEC)
        prompt = _prompt(8, seed=2)                 # exactly 2 blocks
        a = alloc.admit_prompt(0, 0, prompt, max_new=2)
        b = alloc.admit_prompt(1, 0, prompt, max_new=2)
        assert b.cow_src == a.table[1] and b.cow_dst == b.table[1]
        assert b.table[0] == a.table[0] and b.table[1] != a.table[1]
        assert b.matched == 7, "last token always re-prefills"
        assert alloc.cow_copies == 1

    def test_exhaustion_rejects_without_touching_live_state(self):
        alloc = kv_cache.BlockAllocator(self.SPEC)   # 8 blocks
        a = alloc.admit_prompt(0, 0, _prompt(13, seed=3), max_new=2)
        alloc.admit_prompt(1, 0, _prompt(13, seed=4), max_new=2)
        assert alloc.available(0) == 0 and alloc.blocks_in_use() == 8
        with pytest.raises(PoolExhausted):
            alloc.admit_prompt(2, 0, _prompt(13, seed=5), max_new=2)
        assert not alloc.can_admit(0, _prompt(13, seed=5), 2)
        # The reject changed nothing for the live slots.
        assert alloc.available(0) == 0 and alloc.blocks_in_use() == 8
        # An evict returns capacity and the queued request admits.
        alloc.release(0, a.table)
        c = alloc.admit_prompt(2, 0, _prompt(13, seed=5), max_new=2)
        assert len(c.table) == 4

    def test_lru_reclaim_under_pressure(self):
        alloc = kv_cache.BlockAllocator(self.SPEC)
        p1 = _prompt(8, seed=5)
        a = alloc.admit_prompt(0, 0, p1, max_new=0)
        alloc.release(0, a.table)
        assert len(alloc.match_prefix(0, p1)[0]) == 2   # retained
        # A request needing all 8 blocks reclaims the retained ones.
        b = alloc.admit_prompt(1, 0, _prompt(15, seed=6), max_new=1)
        assert len(b.table) == 4
        alloc.admit_prompt(2, 0, _prompt(15, seed=7), max_new=1)
        assert alloc.match_prefix(0, p1)[0] == [], "reclaimed"
        assert alloc.reclaimed > 0


# --------------------------------------------------------------------- #
# What one stream's writes may touch (fp32)
# --------------------------------------------------------------------- #
class TestPagedParity:
    def test_full_context_stream_writes_no_row(self, params32):
        """A stream whose context is at max_seq_len has nowhere to put
        another row: its position resolves past its block table, so the
        decode step writes nothing for it — not into its own blocks, not
        into its group neighbour's, whose own row still lands."""
        eng = _engine(params32, slots=16, max_len=32, block_size=16)
        assert eng.group_of(0) == eng.group_of(1)
        for slot in (0, 1):
            prompt = _prompt(20, seed=30 + slot)
            tok, _ = eng.prefill(prompt, slot=slot)
            eng.activate_slot(slot, len(prompt), tok)
        eng.lengths[0] = eng.max_len                # slot 0 is full
        pos1 = int(eng.lengths[1])

        def pools():
            return [np.array(kv_cache.paged_logical_view(
                eng.cache[n], CFG32.head_dim)) for n in ("k", "v")]

        before = pools()
        eng.decode_once()
        for was, now in zip(before, pools()):
            # [L, G, B, nH, bs, D] -> which (group, block, offset) moved
            moved = (was != now).any(axis=(0, 3, 5))
            assert np.argwhere(moved).tolist() == [
                [eng.group_of(1), int(eng.block_tables[1][pos1 // 16]),
                 pos1 % 16]]
        eng.close()

    def test_cow_fork_isolates_divergent_decode(self, params32):
        """The copy-on-write fork: two identical prompts share all full
        blocks; the forked slot's decode appends must not leak into the
        original's attention."""
        eng = _engine(params32, slots=16, block_size=8)
        prompt = _prompt(16, seed=9)                # exactly 2 blocks
        tok_a, lg_a = eng.prefill(prompt, slot=0, return_logits=True)
        eng.activate_slot(0, len(prompt), tok_a)
        tok_b, lg_b = eng.prefill(prompt, slot=1, return_logits=True)
        eng.activate_slot(1, len(prompt), tok_b)
        assert eng.allocator.cow_copies == 1
        assert eng.block_tables[0][0] == eng.block_tables[1][0]
        assert eng.block_tables[0][1] != eng.block_tables[1][1]
        np.testing.assert_allclose(lg_a, lg_b, atol=1e-5)
        # Force divergence: feed slot 1 a DIFFERENT pending token (the
        # first divergent token goes through the forked private block).
        eng.last_tokens[1] = (tok_b + 1) % CFG32.vocab_size
        seq_a = list(prompt) + [tok_a]
        seq_b = list(prompt) + [int(eng.last_tokens[1])]
        for _ in range(5):
            sampled, lg = eng.decode_once(return_logits=True)
            for slot, seq in ((0, seq_a), (1, seq_b)):
                ref = np.asarray(gpt2_apply(
                    params32,
                    jnp.asarray(np.asarray(seq, np.int32))[None],
                    CFG32))[0, -1]
                np.testing.assert_allclose(lg[slot], ref, atol=1e-4)
                seq.append(int(sampled[slot]))
        assert seq_a[len(prompt) + 1:] != seq_b[len(prompt) + 1:] or \
            seq_a != seq_b
        eng.close()

    def test_prefill_many_matches_sequential(self, params32):
        """Batched one-slot-per-group admission == one-at-a-time."""
        batched = _engine(params32, slots=8, block_size=16)
        seq = _engine(params32, slots=8, block_size=16)
        prompts = [_prompt(7 + i, seed=20 + i) for i in range(4)]
        # Slots 0..3 live in distinct groups (slots_per_group == 1).
        results = batched.prefill_many(
            [(i, p, 4) for i, p in enumerate(prompts)],
            return_logits=True)
        for i, p in enumerate(prompts):
            tok, lg = seq.prefill(p, slot=i, return_logits=True)
            assert results[i][0] == tok
            np.testing.assert_allclose(results[i][1], lg, atol=1e-5)
        batched.close()
        seq.close()

    def test_prefill_is_prefill_many_of_one(self, params32):
        """A prompt enters the cache one way: ``prefill`` is the
        one-admission form of ``prefill_many`` (several chunks here),
        and its logits are the full forward's."""
        eng = _engine(params32, max_len=32, chunk=8, block_size=16)
        prompt = _prompt(19, seed=10)
        tok, logits = eng.prefill(prompt, slot=2, return_logits=True,
                                  max_new_tokens=3)
        ref = np.asarray(gpt2_apply(
            params32, jnp.asarray(prompt)[None], CFG32))[0, -1]
        np.testing.assert_allclose(logits, ref, atol=1e-4)
        assert eng.last_admit_info(2)["chunks"] == 3
        eng.release_slot(2)
        (tok2, logits2), = eng.prefill_many([(2, prompt, 3)],
                                            return_logits=True)
        assert tok2 == tok
        # the second admission rides the first's cached block
        assert eng.last_admit_info(2)["cached_tokens"] == 16
        np.testing.assert_allclose(logits2, logits, atol=1e-5)
        eng.close()


# --------------------------------------------------------------------- #
# Pool exhaustion through the scheduler: reject, queue, recover
# --------------------------------------------------------------------- #
class TestAdmissionGate:
    def test_exhaustion_queues_and_recovers(self, params):
        """A pool sized for ~2 concurrent requests serves 4: the third
        admission is REJECTED while two run (free-block accounting),
        then admitted once a slot evicts and returns its blocks. Every
        request completes; zero recompiles."""
        eng = _engine(params, cfg=CFG, slots=16, max_len=64, chunk=8,
                      block_size=8, num_blocks=16,
                      telemetry={"enabled": True,
                                 "output_path": "/tmp/_paged_gate",
                                 "job_name": "gate",
                                 "report_steps": 10 ** 6,
                                 "fail_on_recompile": True})
        # 16 blocks over 8 groups = 2/group; slots_per_group = 2. Each
        # request needs ceil((12 + 4)/8) = 2 blocks -> one per group at
        # a time; 16 slots but HBM for only 8 concurrent requests.
        reqs = synthetic_requests(12, prompt_len=(10, 12),
                                  max_new_tokens=4,
                                  vocab_size=CFG.vocab_size, seed=11)
        report = eng.serve(reqs)
        assert report["completed"] == 12 and report["unfinished"] == 0
        assert report["recompiles"] == 0
        assert not eng.active.any()
        assert eng.allocator.blocks_in_use() == 0
        eng.close()

    def test_never_admittable_raises_instead_of_hanging(self, params):
        eng = _engine(params, cfg=CFG, slots=8, max_len=64, chunk=8,
                      block_size=8, num_blocks=8)   # 1 block/group
        reqs = synthetic_requests(1, prompt_len=(20, 20),
                                  max_new_tokens=8,
                                  vocab_size=CFG.vocab_size, seed=12)
        with pytest.raises(RuntimeError, match="never be admitted"):
            eng.serve(reqs)
        eng.close()

    def test_select_slot_prefers_prefix_affinity_group(self, params32):
        eng = _engine(params32, slots=16, block_size=8)
        prompt = _prompt(17, seed=13)
        tok, _ = eng.prefill(prompt, slot=5, max_new_tokens=4,
                             return_logits=False)
        eng.activate_slot(5, len(prompt), tok)
        # Slot 5 lives in group 2 (slots_per_group=2); a same-prefix
        # admission must land there.
        slot = eng.select_slot(prompt, max_new_tokens=4)
        assert slot is not None and eng.group_of(slot) == \
            eng.group_of(5)
        assert eng.prefix_match_tokens(prompt) == 16
        eng.close()


# --------------------------------------------------------------------- #
# Speculative decoding
# --------------------------------------------------------------------- #
class TestSpeculativeDecoding:
    def test_drafter_proposes_continuation_of_repeats(self):
        d = NGramDrafter(k=3, ngram=2)
        d.begin(0, [1, 2, 3, 9, 1, 2])
        assert d.propose(0).tolist() == [3, 9, 1]
        d2 = NGramDrafter(k=2, ngram=3)
        d2.begin(1, [5])
        assert d2.propose(1).tolist() == [5, 5], "repeat-last fallback"
        assert d.match_rate() == 1.0 and d2.match_rate() == 0.0

    def test_greedy_streams_bit_identical(self, params):
        """THE spec-decode acceptance gate: same checkpoint, same
        stream, spec_k 0 vs 4 — token streams must be exactly equal,
        and the spec run must do it in fewer iterations."""
        def run(spec_k):
            eng = _engine(params, cfg=CFG, spec_k=spec_k)
            reqs = synthetic_requests(16, prompt_len=(5, 14),
                                      max_new_tokens=12,
                                      vocab_size=CFG.vocab_size, seed=2)
            rep = eng.serve(reqs)
            snap = eng.serving.snapshot()
            eng.close()
            return rep, snap

        rep0, _ = run(0)
        rep4, snap4 = run(4)
        s0 = {r["rid"]: r["tokens"] for r in rep0["requests"]}
        s4 = {r["rid"]: r["tokens"] for r in rep4["requests"]}
        assert s0 == s4, "speculative greedy diverged from baseline"
        assert rep4["iterations"] < rep0["iterations"]
        assert rep0["recompiles"] == 0 and rep4["recompiles"] == 0
        spec = snap4["spec"]
        assert spec["proposed"] > 0
        assert 0.0 <= spec["acceptance_rate"] <= 1.0

    def test_verify_near_slot_capacity_caps_cleanly(self, params):
        """Speculation at the slot boundary: accepted tokens past
        max_len are dropped, lengths never exceed capacity, and the
        stream still matches baseline."""
        def run(spec_k):
            eng = _engine(params, cfg=CFG, max_len=32, spec_k=spec_k,
                          block_size=16)
            reqs = synthetic_requests(4, prompt_len=(24, 26),
                                      max_new_tokens=16,
                                      vocab_size=CFG.vocab_size,
                                      seed=14)
            rep = eng.serve(reqs)
            assert (eng.lengths == 0).all()
            eng.close()
            return {r["rid"]: r["tokens"] for r in rep["requests"]}

        assert run(0) == run(4)

    def test_temperature_falls_back_to_plain_decode(self, params):
        eng = _engine(params, cfg=CFG, spec_k=4)
        with pytest.raises(ValueError, match="greedy-only"):
            eng.spec_decode_once(temperature=0.7)
        reqs = synthetic_requests(4, prompt_len=(5, 8),
                                  max_new_tokens=4,
                                  vocab_size=CFG.vocab_size, seed=15)
        rep = eng.serve(reqs, temperature=1.0)
        assert rep["completed"] == 4
        assert "spec" not in eng.serving.snapshot(), \
            "sampling stream must not use the greedy acceptance rule"
        eng.close()


# --------------------------------------------------------------------- #
# Multi-replica router
# --------------------------------------------------------------------- #
class TestReplicaRouter:
    def test_two_replicas_balance_and_stay_labeled(self, params):
        engines = [InferenceEngine(CFG, params, config={
            "inference": {"max_slots": 8, "max_seq_len": 64,
                          "prefill_chunk": 8, "spec_k": 4,
                          "replica": f"r{i}"}}) for i in range(2)]
        reqs = shared_prefix_requests(20, prefix_len=32,
                                      tail_len=(4, 10),
                                      max_new_tokens=8,
                                      vocab_size=CFG.vocab_size, seed=3)
        rep = ReplicaRouter(engines, temperature=0.0).serve(reqs)
        assert rep["completed"] == 20 and rep["unfinished"] == 0
        assert rep["recompiles"] == 0
        assert sorted(r["replica"] for r in rep["replicas"]) == \
            ["r0", "r1"]
        assert sum(rep["router"]["routed"]) == 20
        assert min(rep["router"]["routed"]) > 0, "load balanced"
        # Every request names its replica; aggregate pools them.
        assert {r["replica"] for r in rep["requests"]} == {0, 1}
        assert rep["ttft_ms"]["n"] == 20
        assert rep["prefix"]["hit_rate"] > 0, "shared prefixes hit"
        for e in engines:
            e.close()

    def test_affinity_routes_to_prefix_holder(self, params32):
        engines = [_engine(params32, slots=8, block_size=8)
                   for _ in range(2)]
        prompt = _prompt(24, seed=16)
        tok, _ = engines[1].prefill(prompt, slot=0, return_logits=False)
        engines[1].activate_slot(0, len(prompt), tok)
        router = ReplicaRouter(engines, affinity_weight=1.0)
        from deepspeed_tpu.inference import Request
        from collections import deque
        req = Request(rid=0, prompt=prompt, max_new_tokens=4)
        assert router.route(req, [deque(), deque()]) == 1
        for e in engines:
            e.close()

    def test_router_never_admittable_raises_instead_of_hanging(
            self, params32):
        engines = [_engine(params32, slots=8, max_len=64, block_size=8,
                           num_blocks=8) for _ in range(2)]
        from deepspeed_tpu.inference import Request
        reqs = [Request(rid=0, prompt=_prompt(20, seed=30),
                        max_new_tokens=8)]
        with pytest.raises(RuntimeError, match="never be admitted"):
            ReplicaRouter(engines).serve(reqs)
        for e in engines:
            e.close()

    def test_aggregator_merged_pools_raw_samples(self):
        a = ServingAggregator(8, label="r0")
        b = ServingAggregator(8, label="r1")
        for ms in (10, 20, 30):
            a.note_request(ms / 1e3, None, 4)
        for ms in (100, 200, 300):
            b.note_request(ms / 1e3, None, 4)
        a.note_iteration(8, 0.01, cache_bytes=1000, context_tokens=10)
        b.note_iteration(4, 0.01, cache_bytes=3000, context_tokens=10)
        m = ServingAggregator.merged([a, b])
        snap = m.snapshot(wall_s=1.0)
        assert snap["replica"] == "aggregate"
        assert snap["completed"] == 6
        assert snap["ttft_ms"]["n"] == 6
        # Pooled median sits between the two replicas' medians.
        assert 20 <= snap["ttft_ms"]["p50"] <= 200
        assert snap["occupancy_mean"] == pytest.approx(0.75)
        assert snap["hbm_bytes_per_token"]["n"] == 2
        assert a.snapshot()["replica"] == "r0"


# --------------------------------------------------------------------- #
# Workloads and config knobs
# --------------------------------------------------------------------- #
class TestWorkloadsAndConfig:
    def test_shared_prefix_requests_share_exactly_the_prefix(self):
        reqs = shared_prefix_requests(6, prefix_len=16, tail_len=(2, 5),
                                      seed=4)
        p0 = reqs[0].prompt[:16]
        for r in reqs:
            assert (r.prompt[:16] == p0).all()
            assert 18 <= len(r.prompt) <= 21
        again = shared_prefix_requests(6, prefix_len=16,
                                       tail_len=(2, 5), seed=4)
        assert all((a.prompt == b.prompt).all()
                   for a, b in zip(reqs, again))

    def test_inference_knob_defaults(self):
        from deepspeed_tpu.runtime.config import InferenceConfig
        inf = InferenceConfig(None)
        assert inf.block_size == 16 and inf.num_blocks == 0
        assert inf.spec_k == 0 and inf.kv_cache_dtype == "model"

    @pytest.mark.parametrize("bad,match", [
        ({"block_size": 0}, "paged block pool is the only KV layout"),
        ({"block_size": -1}, "paged block pool is the only KV layout"),
        ({"spec_k": -2}, "spec_k"),
        ({"kv_cache_dtype": "fp8"}, "kv_cache_dtype"),
        ({"replica": 3}, "replica"),
        ({"num_blocks": -4}, "num_blocks"),
        ({"spec_ngram": 0}, "spec_ngram")])
    def test_bad_inference_knob_is_refused(self, bad, match):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  InferenceConfig)
        with pytest.raises(DeepSpeedConfigError, match=match):
            InferenceConfig({"inference": bad})

    def test_engine_geometry_validation(self, params32):
        with pytest.raises(ValueError, match="block_size"):
            _engine(params32, max_len=40, block_size=16)
        with pytest.raises(ValueError, match="divisible"):
            _engine(params32, block_size=16, num_blocks=12)

    def test_bf16_kv_pool_serves(self, params32):
        eng = InferenceEngine(CFG32, params32, config={
            "inference": {"max_slots": 8, "max_seq_len": 32,
                          "prefill_chunk": 8,
                          "kv_cache_dtype": "bf16"}})
        assert eng.cache["k"].dtype == jnp.bfloat16
        prompt = _prompt(9, seed=17)
        tok, logits = eng.prefill(prompt, slot=0, return_logits=True)
        ref = np.asarray(gpt2_apply(
            params32, jnp.asarray(prompt)[None], CFG32))[0, -1]
        assert np.isfinite(logits).all()
        assert np.corrcoef(logits, ref)[0, 1] > 0.999
        eng.close()


# --------------------------------------------------------------------- #
# The paged serving stream under the sentinel + lint (tier-1 gate)
# --------------------------------------------------------------------- #
class TestPagedServingStream:
    def test_shared_prefix_stream_zero_recompiles_and_lint_clean(
            self, tmp_path, params):
        eng = InferenceEngine(CFG, params, config={
            "inference": {"max_slots": 8, "max_seq_len": 64,
                          "prefill_chunk": 8, "block_size": 8,
                          "spec_k": 3},
            "telemetry": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "paged_serve",
                          "report_steps": 10 ** 6,
                          "fail_on_recompile": True}})
        # Deterministic copy-on-write exercise first: admit a 4-full-
        # block prompt, evict (blocks LRU-retained), re-admit the SAME
        # prompt — the exact-chain match forks its last block, so the
        # copy_block path compiles and registers with the sentinel.
        p32 = _prompt(32, seed=50, vocab=CFG.vocab_size)
        tok, _ = eng.prefill(p32, slot=0)
        eng.activate_slot(0, 32, tok)
        eng.release_slot(0)
        tok, _ = eng.prefill(p32, slot=0)
        eng.activate_slot(0, 32, tok)
        eng.release_slot(0)
        assert eng.allocator.cow_copies == 1
        reqs = shared_prefix_requests(16, prefix_len=24,
                                      tail_len=(3, 9),
                                      max_new_tokens=6,
                                      vocab_size=CFG.vocab_size, seed=5)
        report = eng.serve(reqs)
        assert report["completed"] == 16 and report["unfinished"] == 0
        assert report["recompiles"] == 0
        assert eng.telemetry.recompile_count == 0
        snap = eng.serving.snapshot()
        assert snap["prefix"]["hit_rate"] > 0
        assert snap["hbm_bytes_per_token"]["n"] > 0
        assert snap["spec"]["proposed"] > 0
        # Every compiled path this serve used registered (a spec-k
        # engine decodes THROUGH the verify step, so plain decode_step
        # never compiles); host_sync + materialization CLEAN — no
        # full-pool gather, no in-step host transfer, even through the
        # verify and CoW-copy paths.
        lint = eng.lint_audit(passes=("host_sync", "materialization"))
        assert {p.name for p in lint.paths} == \
            {"prefill_step", "verify_step", "copy_block"}
        assert not lint.unwaived and \
            not any(p.errors for p in lint.paths)
        eng.close()

"""Pallas block-size autotuner (ops/autotune): the determinism contract.

Three independent guarantees, each load-bearing for tier-1:

1. GATING — ``DS_AUTOTUNE=0`` reproduces today's heuristic tiles
   bit-for-bit (no registry read, no search), and a plain CPU process
   never searches even with autotuning on: ``search_allowed()`` is the
   single gate, and ``DS_AUTOTUNE_FORCE=1`` is the explicit test-only
   override these tests use to exercise the search path off-TPU.

2. REGISTRY — first resolve of a key times the candidate grid once and
   persists the winner atomically (tmp + os.replace, no torn files);
   the second resolve — same process or a fresh one — returns the
   winner with ZERO measure calls.  A corrupt registry degrades to
   empty with a warning; a stale entry outside today's legal candidate
   grid is ignored rather than trusted.

3. NUMERICS — tiles move the schedule, not the arithmetic: the fused
   LN kernels produce bitwise-identical outputs under different
   pinned row blocks, which is what makes a shared on-disk tile cache
   safe at all.
"""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capability import fused_elementwise_skip_reason
from deepspeed_tpu.ops import autotune


@pytest.fixture
def registry(tmp_path, monkeypatch):
    """Fresh on-disk registry + force-enabled search, zeroed counters."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("DS_AUTOTUNE_REGISTRY", path)
    monkeypatch.setenv("DS_AUTOTUNE_FORCE", "1")
    monkeypatch.delenv("DS_AUTOTUNE", raising=False)
    autotune.reset()
    yield path
    autotune.reset()


class CountingMeasure:
    """measure(tile) stub: deterministic timings, call accounting."""

    def __init__(self, timings):
        self.timings = dict(timings)
        self.calls = []

    def __call__(self, tile):
        self.calls.append(tile)
        try:
            return self.timings[tile]
        except KeyError:
            raise RuntimeError(f"candidate {tile} does not compile")


class TestGating:
    def test_disabled_returns_heuristic(self, registry, monkeypatch):
        monkeypatch.setenv("DS_AUTOTUNE", "0")
        meas = CountingMeasure({32: 0.1, 64: 0.5})
        got = autotune.resolve("k", (8, 128), "float32", 64,
                               (32, 64), meas)
        assert got == 64
        assert meas.calls == []          # no search
        assert not os.path.exists(registry)   # no registry write
        assert autotune.counters["heuristic"] == 1
        assert not autotune.enabled() and not autotune.search_allowed()

    def test_cpu_without_force_never_searches(self, registry, monkeypatch):
        monkeypatch.delenv("DS_AUTOTUNE_FORCE", raising=False)
        if jax.default_backend() == "tpu":
            pytest.skip("gate under test is the off-TPU default")
        assert autotune.enabled() and not autotune.search_allowed()
        meas = CountingMeasure({32: 0.1, 64: 0.5})
        got = autotune.resolve("k", (8, 128), "float32", 64,
                               (32, 64), meas)
        assert got == 64 and meas.calls == []
        assert not os.path.exists(registry)

    def test_disabled_geom_matches_budget_loop(self, registry, monkeypatch):
        """DS_AUTOTUNE=0 -> _geom reproduces the static VMEM budget loop
        (today's tiles, bit-for-bit) for every kernel'd call site."""
        monkeypatch.setenv("DS_AUTOTUNE", "0")
        from deepspeed_tpu.ops.fused_elementwise import (_LANE, _VMEM_BUDGET,
                                                         _geom)
        for rows, H, n_bufs in [(64, 768, 5), (512, 3072, 4),
                                (8, 65536, 7), (1024, 128, 6)]:
            Hpad = -(-H // _LANE) * _LANE
            rb = 128
            while rb > 16 and rb * Hpad * 4 * n_bufs > _VMEM_BUDGET:
                rb //= 2
            got = _geom(rows, H, n_bufs, kernel="fused_ln_fwd",
                        dtype=jnp.float32, runner=None)
            assert got == (-(-rows // rb) * rb, Hpad, rb)

    def test_disabled_flash_blocks_match_pick_block(self, registry,
                                                    monkeypatch):
        monkeypatch.setenv("DS_AUTOTUNE", "0")
        from deepspeed_tpu.ops.flash_attention import (_BLOCK_TARGET,
                                                       _pick_block)
        for s in (128, 512, 1024, 4096):
            b = _pick_block(s)
            assert s % b == 0 and b <= max(s, _BLOCK_TARGET)


class TestRegistry:
    def test_search_once_then_registry_hit(self, registry):
        meas = CountingMeasure({32: 0.01, 64: 0.05, 128: 0.03})
        got = autotune.resolve("fused_ln_fwd", (512, 768, 5), "float32",
                               64, (32, 64, 128), meas)
        assert got == 32                     # fastest, not the heuristic
        assert sorted(meas.calls) == [32, 64, 128]
        assert autotune.counters["search"] == 1

        # Second resolve, same process: zero measure calls.
        meas2 = CountingMeasure({})
        got2 = autotune.resolve("fused_ln_fwd", (512, 768, 5), "float32",
                                64, (32, 64, 128), meas2)
        assert got2 == 32 and meas2.calls == []
        assert autotune.counters["hit"] == 1

        # Fresh process (in-memory cache dropped): served from disk.
        autotune._CACHE.clear()
        got3 = autotune.resolve("fused_ln_fwd", (512, 768, 5), "float32",
                                64, (32, 64, 128), meas2)
        assert got3 == 32 and meas2.calls == []

    def test_registry_file_shape_and_atomicity(self, registry):
        meas = CountingMeasure({(128, 128): 0.02, (256, 128): 0.01})
        got = autotune.resolve("grouped_gemm", (8, 256, 512, 1024),
                               "bfloat16", (128, 128),
                               [(128, 128), (256, 128)], meas)
        assert got == (256, 128)
        with open(registry) as f:
            reg = json.load(f)
        key = f"grouped_gemm|bfloat16[8,256,512,1024]|{autotune.chip_kind()}"
        ent = reg[key]
        assert ent["tile"] == [256, 128]
        assert ent["heuristic"] == [128, 128]
        assert ent["speedup_vs_heuristic"] == 2.0
        assert set(ent["timings_s"]) == {"(128, 128)", "(256, 128)"}
        # Atomic write: no temp droppings next to the registry.
        leftovers = [p for p in os.listdir(os.path.dirname(registry))
                     if p.startswith(".autotune_")]
        assert leftovers == []

        # Tuple roundtrip through JSON back to the call-site type.
        autotune._CACHE.clear()
        got2 = autotune.resolve("grouped_gemm", (8, 256, 512, 1024),
                                "bfloat16", (128, 128),
                                [(128, 128), (256, 128)],
                                CountingMeasure({}))
        assert got2 == (256, 128) and isinstance(got2, tuple)

    def test_corrupt_registry_degrades_to_empty(self, registry):
        with open(registry, "w") as f:
            f.write("{ this is not json")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            got = autotune.resolve("k", (4, 4), "float32", 64, (64,), None)
        assert got == 64
        assert any("unreadable" in str(x.message) for x in w)
        # And a search afterwards rewrites a VALID file over the wreck.
        meas = CountingMeasure({32: 0.01, 64: 0.02})
        assert autotune.resolve("k", (4, 4), "float32", 64,
                                (32, 64), meas) == 32
        with open(registry) as f:
            assert json.load(f)  # parses again

    def test_stale_entry_outside_grid_is_ignored(self, registry):
        with open(registry, "w") as f:
            json.dump({f"k|float32[4,4]|{autotune.chip_kind()}":
                       {"tile": 999}}, f)
        meas = CountingMeasure({32: 0.02, 64: 0.01})
        got = autotune.resolve("k", (4, 4), "float32", 64, (32, 64), meas)
        assert got == 64                 # re-searched, 999 not trusted
        assert sorted(meas.calls) == [32, 64]

    def test_failing_candidate_is_discarded(self, registry):
        meas = CountingMeasure({64: 0.02})   # 32 raises (no compile)
        got = autotune.resolve("k", (9, 9), "float32", 64, (32, 64), meas)
        assert got == 64
        assert sorted(meas.calls) == [32, 64]

    def test_every_candidate_failing_is_an_error(self, registry):
        """A search in which nothing ran is not a quiet heuristic."""
        meas = CountingMeasure({})           # every candidate raises
        with pytest.raises(RuntimeError, match="every candidate failed"):
            autotune.resolve("k", (7, 7), "float32", 64, (32, 64), meas)
        assert sorted(meas.calls) == [32, 64]
        assert not os.path.exists(registry)

    def test_no_search_under_a_trace(self, registry):
        """Under jit a runner's arrays are tracers and the clock would
        read tracing: the registry or the heuristic answers, nothing is
        timed; the same call made eagerly searches."""
        meas = CountingMeasure({32: 0.01, 64: 0.05})
        picked = []

        @jax.jit
        def traced(x):
            picked.append(autotune.resolve("k", (5, 5), "float32", 64,
                                           (32, 64), meas))
            return x

        traced(jnp.zeros(()))
        assert picked == [64] and meas.calls == []
        assert autotune.counters["search"] == 0
        assert autotune.resolve("k", (5, 5), "float32", 64, (32, 64),
                                meas) == 32
        assert autotune.counters["search"] == 1
        picked.clear()
        jax.jit(lambda x: traced.__wrapped__(x))(jnp.zeros(()))
        assert picked == [32]                # traced resolve HITS it

    def test_runner_returning_tracers_is_refused(self, registry):
        measure = autotune.measure_from_runner(lambda tile: jnp.zeros(()))

        @jax.jit
        def traced(x):
            with pytest.raises(RuntimeError, match="tracers"):
                measure(32)
            return x

        traced(jnp.zeros(()))

    def test_registry_sits_beside_the_compile_cache(self, monkeypatch,
                                                    tmp_path):
        from deepspeed_tpu.utils import compile_cache
        monkeypatch.delenv("DS_AUTOTUNE_REGISTRY", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.cache_dir() == os.path.join(repo, ".jax_cache")
        assert autotune.registry_path() == os.path.join(
            repo, ".jax_cache", "autotune.json")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.cache_dir() == str(tmp_path)
        assert autotune.registry_path() == str(tmp_path / "autotune.json")

    def test_no_measure_returns_heuristic_without_record(self, registry):
        got = autotune.resolve("k", (3, 3), "float32", 64, (32, 64), None)
        assert got == 64
        assert not os.path.exists(registry)
        assert autotune.counters["heuristic"] == 1

    def test_pow2_candidates_respects_budget(self):
        assert autotune.pow2_candidates(16, 256) == (16, 32, 64, 128, 256)
        assert autotune.pow2_candidates(16, 256, lambda c: c <= 64) == \
            (16, 32, 64)
        assert autotune.pow2_candidates(200, 100) == ()


@pytest.mark.skipif(fused_elementwise_skip_reason() is not None,
                    reason=fused_elementwise_skip_reason() or "")
class TestTileBitIdentity:
    """Tiles move the schedule, not the arithmetic — the property that
    makes a shared tile registry safe."""

    def _rand(self, shape, seed, dtype=jnp.float32):
        r = np.random.default_rng(seed)
        return jnp.asarray(r.standard_normal(shape),
                           jnp.float32).astype(dtype)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_ln_forward_bitwise_across_row_blocks(self, dtype):
        from deepspeed_tpu.ops.fused_elementwise import _ln_forward
        x = self._rand((256, 384), 0, dtype)
        sc = self._rand((384,), 1)
        bi = self._rand((384,), 2)
        outs = [_ln_forward(x, None, sc, bi, 1e-5, _rb=rb)[1]
                for rb in (32, 128)]
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))

    def test_grouped_gemm_bitwise_across_tiles(self):
        from deepspeed_tpu.ops.grouped_gemm import _grouped_matmul
        a = self._rand((4, 64, 96), 5)
        b = self._rand((4, 96, 256), 6)
        outs = [np.asarray(_grouped_matmul(a, b, _tile=t))
                for t in ((32, 128), (64, 256))]
        np.testing.assert_array_equal(outs[0], outs[1])

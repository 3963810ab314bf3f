"""Serving tier tests: incremental decode parity, continuous
batching under the recompile-sentinel gate, quantization, and the
training-checkpoint handoff.

The two load-bearing invariants:

1. **Exactness** — decode against the paged cache produces the SAME
   logits as the full forward at the growing sequence's final position,
   asserted per step (fp32 config, float tolerance: the incremental
   path contracts in a different order).
2. **Static shapes** — a synthetic open-loop arrival stream with
   requests joining and leaving mid-flight (varying active counts,
   varying prompt lengths, varying generation lengths) compiles the
   decode and prefill programs ONCE each; ``fail_on_recompile`` is
   armed, so any shape polymorphism dies loudly here.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingScheduler,
                                     InferenceEngine, synthetic_requests)
from deepspeed_tpu.inference.quantize import (dequantize,
                                              quantize_leaf_int8,
                                              quantize_params)
from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_apply, gpt2_init,
                                       gpt2_logits_at, gpt2_param_shardings)
from deepspeed_tpu.parallel.topology import build_mesh

CFG32 = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def params32():
    return gpt2_init(jax.random.PRNGKey(0), CFG32)


def _prompt(n, seed=0, vocab=None):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab or CFG32.vocab_size,
                        size=n).astype(np.int32)


def _ref_last_logits(params, seq):
    toks = jnp.asarray(np.asarray(seq, np.int32))[None]
    return np.asarray(gpt2_apply(params, toks, CFG32))[0, -1]


# --------------------------------------------------------------------- #
# Satellite: last-position-only logits
# --------------------------------------------------------------------- #
class TestGpt2LogitsAt:
    def test_matches_full_apply_final_position(self, params32):
        toks = jnp.asarray(_prompt(9, seed=1).reshape(1, 9))
        full = gpt2_apply(params32, toks, CFG32)
        at = gpt2_logits_at(params32, toks, CFG32)
        np.testing.assert_allclose(np.asarray(at), np.asarray(full[:, -1]),
                                   atol=1e-5)

    def test_traced_index(self, params32):
        """The prefill path indexes the prompt's final token inside a
        jitted program — the index must be traceable."""
        toks = jnp.asarray(_prompt(9, seed=2).reshape(1, 9))
        full = np.asarray(gpt2_apply(params32, toks, CFG32))
        fn = jax.jit(lambda t, i: gpt2_logits_at(params32, t, CFG32,
                                                 index=i))
        for i in (0, 4, 8):
            np.testing.assert_allclose(np.asarray(fn(toks, jnp.int32(i))),
                                       full[:, i], atol=1e-5)

    def test_traced_negative_index_normalizes(self, params32):
        """dynamic_index_in_dim CLAMPS a negative traced index to 0 —
        the from-the-end semantics must survive tracing."""
        toks = jnp.asarray(_prompt(9, seed=2).reshape(1, 9))
        full = np.asarray(gpt2_apply(params32, toks, CFG32))
        fn = jax.jit(lambda t, i: gpt2_logits_at(params32, t, CFG32,
                                                 index=i))
        np.testing.assert_allclose(np.asarray(fn(toks, jnp.int32(-1))),
                                   full[:, -1], atol=1e-5)

    def test_never_materializes_full_logits(self, params32):
        """The [B, S, vocab] tensor must not appear in the jaxpr."""
        toks = jnp.asarray(_prompt(16, seed=3).reshape(1, 16))
        jaxpr = jax.make_jaxpr(
            lambda t: gpt2_logits_at(params32, t, CFG32))(toks)
        full_shape = (1, 16, CFG32.vocab_size)
        assert all(getattr(v.aval, "shape", None) != full_shape
                   for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars)


# --------------------------------------------------------------------- #
# Decode-vs-full-forward parity (the exactness gate)
# --------------------------------------------------------------------- #
class TestDecodeParity:
    @pytest.fixture(scope="class")
    def engine(self, params32):
        eng = InferenceEngine(CFG32, params32, config={
            "inference": {"max_slots": 8, "max_seq_len": 64,
                          "prefill_chunk": 8}})
        yield eng
        eng.close()

    def test_prefill_then_decode_matches_full_forward(self, engine,
                                                      params32):
        """Per-step: incremental logits == full forward's final
        position, for a prompt that does NOT divide the chunk."""
        prompt = _prompt(11, seed=4)
        tok, logits = engine.prefill(prompt, slot=0, return_logits=True)
        ref = _ref_last_logits(params32, prompt)
        np.testing.assert_allclose(logits, ref, atol=1e-4)
        assert tok == int(ref.argmax())
        engine.activate_slot(0, len(prompt), tok)
        seq = list(prompt) + [tok]
        for _ in range(6):
            sampled, lg = engine.decode_once(return_logits=True)
            np.testing.assert_allclose(lg[0],
                                       _ref_last_logits(params32, seq),
                                       atol=1e-4)
            seq.append(int(sampled[0]))
        engine.release_slot(0)

    def test_concurrent_slots_are_isolated(self, engine, params32):
        """Two slots with different prompts decode independently —
        each matches its own full forward."""
        p_a, p_b = _prompt(7, seed=5), _prompt(13, seed=6)
        tok_a, _ = engine.prefill(p_a, slot=1)
        tok_b, _ = engine.prefill(p_b, slot=5)
        engine.activate_slot(1, len(p_a), tok_a)
        engine.activate_slot(5, len(p_b), tok_b)
        seq_a, seq_b = list(p_a) + [tok_a], list(p_b) + [tok_b]
        for _ in range(4):
            sampled, lg = engine.decode_once(return_logits=True)
            np.testing.assert_allclose(
                lg[1], _ref_last_logits(params32, seq_a), atol=1e-4)
            np.testing.assert_allclose(
                lg[5], _ref_last_logits(params32, seq_b), atol=1e-4)
            seq_a.append(int(sampled[1]))
            seq_b.append(int(sampled[5]))
        engine.release_slot(1)
        engine.release_slot(5)

    def test_prefill_chunk_zero_is_refused(self, params32):
        """Chunked prefill is the only admission path: ``prefill_chunk:
        0`` (once whole-prompt prefill) is refused by the config and so
        by the engine, which parses it."""
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  InferenceConfig)
        bad = {"inference": {"max_slots": 8, "max_seq_len": 32,
                             "prefill_chunk": 0}}
        with pytest.raises(DeepSpeedConfigError, match="positive int"):
            InferenceConfig(bad)
        with pytest.raises(DeepSpeedConfigError, match="only admission"):
            InferenceEngine(CFG32, params32, config=bad)

    def test_temperature_sampling_reproducible(self, engine):
        """Threaded PRNG: temperature > 0 samples; the in-graph
        categorical is deterministic given the engine's key stream."""
        prompt = _prompt(6, seed=8)
        tok, logits = engine.prefill(prompt, slot=3, temperature=1.0,
                             return_logits=True)
        assert 0 <= tok < CFG32.vocab_size
        assert np.isfinite(logits).all()
        engine.release_slot(3)

    def test_engine_geometry_validation(self, params32):
        with pytest.raises(ValueError, match="divide"):
            InferenceEngine(CFG32, params32, config={
                "inference": {"max_slots": 8, "max_seq_len": 60,
                              "prefill_chunk": 8}})
        with pytest.raises(ValueError, match="position table"):
            InferenceEngine(CFG32, params32, config={
                "inference": {"max_slots": 8, "max_seq_len": 4096}})

    def test_prompt_too_long_raises(self, engine):
        with pytest.raises(ValueError, match="no room"):
            engine.prefill(_prompt(64), slot=0)


# --------------------------------------------------------------------- #
# The serving acceptance gate: continuous batching on the dp=8 mesh
# --------------------------------------------------------------------- #
class TestServingStream:
    def test_open_loop_stream_occupancy_and_zero_recompiles(self, tmp_path):
        """The ROADMAP item-3 acceptance: a synthetic open-loop stream
        with varying prompt lengths AND varying generation lengths
        (requests join/leave mid-flight, so the active-slot count walks
        all over) — occupancy > 80%, ZERO post-warmup recompiles under
        fail_on_recompile, TTFT/TPOT p50/p95 recorded and surfaced by
        the telemetry report's serving section."""
        cfg = GPT2_CONFIGS["gpt2-tiny"]
        eng = InferenceEngine(cfg, gpt2_init(jax.random.PRNGKey(1), cfg),
                              config={
            "inference": {"max_slots": 8, "max_seq_len": 64,
                          "prefill_chunk": 8},
            "telemetry": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "serve",
                          # Larger than the whole serve: the scheduler's
                          # END-of-serve drain must carry the aggregator
                          # snapshot on its own (a run shorter than
                          # report_steps must not lose tokens/s).
                          "report_steps": 10 ** 6,
                          "fail_on_recompile": True}})
        reqs = synthetic_requests(24, prompt_len=(5, 14),
                                  max_new_tokens=8,
                                  vocab_size=cfg.vocab_size, seed=2)
        # Vary generation length too: slots free at different iterations
        # (a 3-deep saturation backlog keeps refills instant, so the
        # drain tail doesn't swamp the occupancy average). The loop runs
        # an iteration ahead of its token fetch, so a freed slot sits out
        # the one iteration already in flight: one row of 13 a reply.
        for i, r in enumerate(reqs):
            r.max_new_tokens = 12 + (i % 3)
        report = eng.serve(reqs)

        assert report["completed"] == 24 and report["unfinished"] == 0
        assert report["occupancy_mean"] > 0.8, report["occupancy_mean"]
        assert report["recompiles"] == 0
        assert eng.telemetry.recompile_count == 0
        for sec in ("ttft_ms", "tpot_ms"):
            assert report[sec]["n"] > 0
            assert report[sec]["p95"] >= report[sec]["p50"] > 0
        for r in report["requests"]:
            assert r["new_tokens"] == 12 + (r["rid"] % 3)
        # Every slot drained.
        assert not eng.active.any() and (eng.lengths == 0).all()

        # The compile-time serving contract: host_sync + materialization
        # clean over both compiled paths (no full-cache gather, no
        # in-step host transfer).
        lint = eng.lint_audit(passes=("host_sync", "materialization"))
        assert {p.name for p in lint.paths} == \
            {"decode_step", "prefill_step"}
        assert not lint.unwaived and not any(p.errors for p in lint.paths)

        eng.close()
        # JSONL → serving section of the report pipeline.
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from telemetry_report import summarize
        summary = summarize(str(tmp_path / "serve.jsonl"))
        srv = summary["serving"]
        assert srv["available"] and srv["completed"] == 24
        assert srv["occupancy_mean"] > 0.8
        assert srv["ttft_ms"]["n"] == 24
        assert summary["recompiles"]["count"] == 0
        assert srv["tokens_per_s"] > 0

    def test_timeout_releases_mid_flight_slots(self):
        """A max_wall_s abort must hand mid-flight slots back — a leak
        here leaves the engine's next serve() with zero capacity. Uses a
        duck-typed fake engine (scheduler logic only, no compiles)."""
        import time as _time

        class _FakeTelemetry:
            enabled = False
            recompile_count = 0

            def span(self, *a, **k):
                # what a disabled Telemetry returns: the bare annotation
                import jax
                return jax.profiler.TraceAnnotation(*a, **k)

        class _FakeEngine:
            max_slots, max_len, prefill_chunk = 2, 1000, 8
            telemetry = _FakeTelemetry()

            def __init__(self):
                self.active = np.zeros(2, bool)
                from deepspeed_tpu.monitor.serving import ServingAggregator
                self.serving = ServingAggregator(2)

            def group_of(self, slot):
                return 0                 # one dp group: one admission a batch

            def select_slot(self, prompt, max_new_tokens=0,
                            exclude_groups=None):
                free = np.flatnonzero(~self.active)
                return int(free[0]) if len(free) and not exclude_groups \
                    else None

            def prefill(self, prompt, slot, temperature=0.0, **kw):
                return 1, None

            def prefill_many(self, admissions, temperature=0.0, rids=None):
                return [self.prefill(prompt, slot, temperature)
                        for slot, prompt, _ in admissions]

            def activate_slot(self, slot, n, tok):
                self.active[slot] = True

            def release_slot(self, slot):
                self.active[slot] = False

            def context_len(self, slot):
                return 10

            def decode_once(self, temperature=0.0, continuing=()):
                # a synchronous engine: the iteration fetched is its own
                self.serving.note_iteration(int(self.active.sum()), 1e-4)
                _time.sleep(0.001)
                return np.ones(2, np.int32), self.active.copy()

            def complete_request(self, *a, **k):
                self.serving.note_request(0.01, None, 1)

        eng = _FakeEngine()
        reqs = [dataclasses.replace(r, max_new_tokens=10 ** 6)
                for r in synthetic_requests(4, prompt_len=(4, 4))]
        sched = ContinuousBatchingScheduler(eng, max_wall_s=0.05)
        report = sched.serve(reqs)
        assert report["unfinished"] > 0          # the abort really hit
        assert not eng.active.any(), "timeout leaked active slots"

    def test_poisson_arrivals_are_open_loop(self):
        reqs = synthetic_requests(10, rate_rps=100.0, seed=3)
        arr = [r.arrival_s for r in reqs]
        assert arr == sorted(arr) and arr[0] == 0.0 and arr[-1] > 0.0
        # Reproducible stream.
        again = synthetic_requests(10, rate_rps=100.0, seed=3)
        assert [r.arrival_s for r in again] == arr
        assert all((r.prompt == a.prompt).all()
                   for r, a in zip(reqs, again))


# --------------------------------------------------------------------- #
# Tensor-parallel serving (TP head-sharded cache)
# --------------------------------------------------------------------- #
class TestTensorParallelServing:
    def test_mp2_decode_matches_full_forward(self, params32):
        mesh = build_mesh(mp=2)           # dp=4 x mp=2
        eng = InferenceEngine(CFG32, params32, config={
            "inference": {"max_slots": 8, "max_seq_len": 32,
                          "prefill_chunk": 8}},
            mesh=mesh, param_shardings=gpt2_param_shardings(CFG32))
        prompt = _prompt(9, seed=9)
        tok, logits = eng.prefill(prompt, slot=0, return_logits=True)
        np.testing.assert_allclose(logits,
                                   _ref_last_logits(params32, prompt),
                                   atol=1e-4)
        eng.activate_slot(0, len(prompt), tok)
        sampled, lg = eng.decode_once(return_logits=True)
        np.testing.assert_allclose(
            lg[0], _ref_last_logits(params32, list(prompt) + [tok]),
            atol=1e-4)
        eng.close()


# --------------------------------------------------------------------- #
# Quantization
# --------------------------------------------------------------------- #
class TestQuantize:
    def test_int8_roundtrip_error_bounded_by_scale(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (16, 24),
                              jnp.float32) * 0.05
        q = quantize_leaf_int8(w, jax.random.PRNGKey(1))
        assert q["q"].dtype == jnp.int8
        dq = np.asarray(q["q"].astype(jnp.float32) * q["scale"])
        scale = np.asarray(q["scale"])
        assert (np.abs(dq - np.asarray(w)) <= scale + 1e-7).all(), \
            "stochastic rounding moves at most one grid step"

    def test_int8_tree_quantizes_matrices_only(self, params32):
        q = quantize_params(params32, "int8", jax.random.PRNGKey(2))
        assert q["blocks"]["qkv_kernel"]["q"].dtype == jnp.int8
        assert q["ln_f_scale"].dtype == jnp.float32, "vectors untouched"
        dq = dequantize(q, jnp.float32)
        w, w0 = np.asarray(dq["wte"]), np.asarray(params32["wte"])
        assert np.abs(w - w0).max() < np.abs(w0).max() / 64

    def test_bf16_mode_uses_stochastic_rounding_machinery(self, params32):
        q = quantize_params(params32, "bf16", jax.random.PRNGKey(3))
        assert all(l.dtype == jnp.bfloat16
                   for l in jax.tree_util.tree_leaves(q))

    def test_int8_engine_serves(self, params32):
        eng = InferenceEngine(CFG32, params32, config={
            "inference": {"max_slots": 8, "max_seq_len": 32,
                          "prefill_chunk": 8, "quantize": "int8"}})
        assert eng.param_bytes < 2 * sum(
            l.size * 4 for l in jax.tree_util.tree_leaves(params32)) / 3
        prompt = _prompt(9, seed=10)
        tok, logits = eng.prefill(prompt, slot=0, return_logits=True)
        assert np.isfinite(logits).all()
        ref = _ref_last_logits(params32, prompt)
        assert np.corrcoef(logits, ref)[0, 1] > 0.99
        eng.close()


# --------------------------------------------------------------------- #
# Training-checkpoint → serving handoff
# --------------------------------------------------------------------- #
class TestCheckpointHandoff:
    def test_from_train_checkpoint_greedy_parity(self, tmp_path,
                                                 params32):
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import gpt2_loss_fn
        trainer, *_ = deepspeed_tpu.initialize(
            model=gpt2_loss_fn(CFG32), model_params=params32,
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "steps_per_print": 10 ** 9})
        trainer.save_checkpoint(str(tmp_path), tag="handoff")
        trained = jax.device_get(trainer.state.params)

        eng = InferenceEngine.from_train_checkpoint(
            str(tmp_path), CFG32, config={
                "inference": {"max_slots": 8, "max_seq_len": 32,
                              "prefill_chunk": 8}})
        prompt = _prompt(7, seed=11)
        tok, logits = eng.prefill(prompt, slot=0, return_logits=True)
        ref = np.asarray(gpt2_apply(
            trained, jnp.asarray(prompt)[None], CFG32))[0, -1]
        np.testing.assert_allclose(logits, ref, atol=1e-4)
        assert tok == int(ref.argmax())
        eng.close()

    def test_missing_checkpoint_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            InferenceEngine.from_train_checkpoint(str(tmp_path), CFG32)

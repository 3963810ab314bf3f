"""Compile-time program auditor (analysis/): the lint suite's tier-1
gates.

The load-bearing assertions (ISSUE 6 acceptance):
- Each lint pass has a seeded-violation test — a deliberately unaliased
  donated buffer, an injected full all-gather under declared ZeRO
  sharding, a forced bf16->f32 round-trip, an in-step pure_callback, and
  a mis-placed collective — each caught by EXACTLY the intended pass.
- The clean engine paths (main/offload/trio on the dp=8 CPU mesh)
  produce zero unwaived findings, and the audit itself issues zero
  device fences (device_sync_count-asserted).
- The waiver machinery: bracket-safe glob matching, stale-waiver
  detection, and LINT_AUDIT.json consistency (every finding priced or
  explicitly unpriced, every waiver matched to a live finding).
"""
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.analysis import hlo_text
from deepspeed_tpu.analysis.auditor import lint_jit
from deepspeed_tpu.analysis.findings import (LintConfig, LintFinding,
                                             Waiver, apply_waivers,
                                             load_waivers)
from deepspeed_tpu.parallel import comm
from deepspeed_tpu.utils import timer as timer_mod

from simple_model import (simple_model_params, simple_loss_fn, random_batch,
                          base_config)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAIVER_FILE = os.path.join(REPO, "tools", "lint_waivers.json")


def _tel(tmp_path, name="lint"):
    return {"enabled": True, "output_path": str(tmp_path),
            "job_name": name, "report_steps": 10 ** 9}


def _engine(tmp_path, name="lint", seed=0, **overrides):
    cfg = base_config(telemetry=_tel(tmp_path, name), **overrides)
    params = simple_model_params(jax.random.PRNGKey(seed))
    engine, *_ = deepspeed_tpu.initialize(
        model=simple_loss_fn, model_params=params, config=cfg)
    return engine


def _lints(result):
    return sorted({f.lint for f in result.findings})


# --------------------------------------------------------------------- #
# Seeded violations: one per pass, caught by exactly the intended pass
# --------------------------------------------------------------------- #
class TestSeededViolations:
    def test_unaliased_donated_buffer_caught_by_donation_pass(self):
        """A donated f32 input returned only as bf16 has no same-aval
        output to alias — the donation freed nothing."""
        def step(state, x):
            return (state * 2.0).astype(jnp.bfloat16), x.sum()

        fn = jax.jit(step, donate_argnums=(0,))
        res = lint_jit(fn, jnp.zeros((256, 256), jnp.float32),
                       jnp.ones((8,), jnp.float32), name="seeded_donation")
        assert not res.errors, res.errors
        assert _lints(res) == ["donation"], [f.fingerprint
                                             for f in res.findings]
        f = res.findings[0]
        assert f.bytes == 256 * 256 * 4
        assert f.priced is False
        assert "alias" in f.summary

    def test_full_gather_under_declared_sharding_caught_by_materialization(
            self, mesh8):
        """Two dp-sharded leaves gathered and concatenated into one
        replicated tree-sized buffer: the ZeRO-3 'XLA materialized the
        full tree' failure, injected."""
        sh = NamedSharding(mesh8, P("data"))
        a = jax.device_put(jnp.ones((1024,), jnp.float32), sh)
        b = jax.device_put(jnp.ones((1024,), jnp.float32), sh)

        def gather_all(a, b):
            full = jnp.concatenate([
                lax.with_sharding_constraint(a, NamedSharding(mesh8, P())),
                lax.with_sharding_constraint(b, NamedSharding(mesh8, P()))])
            # The tree-sized buffer must be a live value (a bare .sum()
            # lets XLA fold the gather into shard-local partials and the
            # injected materialization never happens).
            return full * 2.0

        # declared per-device state: two 1/8 shards; largest single
        # (unsharded) leaf is exempt — the 2-leaf concat is not.
        meta = {"declared_state_bytes": 2 * 1024 * 4 // 8,
                "largest_leaf_bytes": 1024 * 4}
        res = lint_jit(jax.jit(gather_all), a, b, name="seeded_gather",
                       meta=meta)
        assert not res.errors, res.errors
        assert _lints(res) == ["materialization"], \
            [f.fingerprint for f in res.findings]
        assert all(f.bytes >= 2 * 1024 * 4 for f in res.findings)
        assert all(f.priced is False for f in res.findings)

    def test_full_pool_gather_in_serving_path_fires_despite_score_budget(
            self):
        """A serving-shaped path that materializes a per-stream copy of
        the WHOLE block pool ([Q, B, nH, bs, D] — the naive gather the
        one-hot contraction exists to avoid): fires even though the
        engine's ``paged_score_bytes`` budget is declared, because a
        K/V gather is head_dim times the budgeted score transient."""
        B, nH, bs, D, Q, J, K = 32, 2, 8, 16, 4, 4, 1
        pool_k = jnp.ones((B, nH, bs, D), jnp.float32)
        sel = jnp.zeros((Q, J, B), jnp.float32)
        meta = {"declared_state_bytes": 4096,
                "largest_leaf_bytes": 2048,
                "paged_score_bytes": Q * K * nH * B * bs * 4}

        def full_pool_gather(pool_k, sel):
            gathered = pool_k[None] * sel.sum(1)[:, :, None, None, None]
            return gathered * 2.0           # live pool-sized value

        res = lint_jit(jax.jit(full_pool_gather), pool_k, sel,
                       name="seeded_pool_gather", meta=meta)
        assert not res.errors, res.errors
        assert _lints(res) == ["materialization"], \
            [f.fingerprint for f in res.findings]
        assert all(f.bytes >= Q * B * nH * bs * D * 4
                   for f in res.findings)

    def test_onehot_score_transient_rides_its_declared_budget(self):
        """The flip side: the one-hot attend's [Q, K, nH, B, bs] fp32
        score transient passes WITH the ``paged_score_bytes`` budget the
        engine declares on one-hot paths, and fires WITHOUT it — the
        budget is load-bearing, not decorative."""
        # Q*K > D so the [Q,K,nH,B,bs] score transient outweighs the
        # declared pool (the regime the budget exists for: pool growth
        # and wide verify batches inflate the transient past state).
        B, nH, bs, D, Q, K = 32, 2, 8, 4, 8, 2
        q = jnp.ones((Q, K, nH, D), jnp.float32)
        pool_k = jnp.ones((B, nH, bs, D), jnp.float32)

        def score(q, pool_k):
            s = jnp.einsum("qknd,bntd->qknbt", q, pool_k)
            return s * 2.0                  # live score-sized value

        pool_bytes = B * nH * bs * D * 4
        base = {"declared_state_bytes": 2 * pool_bytes,   # K + V pools
                "largest_leaf_bytes": pool_bytes}
        budget = Q * K * nH * B * bs * 4
        clean = lint_jit(jax.jit(score), q, pool_k, name="seeded_score",
                         meta={**base, "paged_score_bytes": budget})
        assert not clean.errors and not clean.findings, \
            [f.fingerprint for f in clean.findings]
        fires = lint_jit(jax.jit(score), q, pool_k,
                         name="seeded_score_nobudget", meta=base)
        assert _lints(fires) == ["materialization"]

    def test_bf16_f32_round_trip_caught_by_dtype_flow(self):
        def loss(x):
            wide = x.astype(jnp.float32)          # forced upcast...
            back = wide.astype(jnp.bfloat16)      # ...cast straight back
            return (back * back).sum()

        res = lint_jit(jax.jit(loss), jnp.ones((64, 64), jnp.bfloat16),
                       name="seeded_roundtrip")
        assert not res.errors, res.errors
        assert _lints(res) == ["dtype_flow"], [f.fingerprint
                                               for f in res.findings]
        f = res.findings[0]
        assert f.key.startswith("bfloat16->float32->bfloat16")
        assert f.bytes == 64 * 64 * 4              # the widened transient

    def test_in_step_pure_callback_caught_by_host_sync(self):
        def step(x):
            y = x.sum()
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), y)

        res = lint_jit(jax.jit(step), jnp.ones((16,), jnp.float32),
                       name="seeded_callback")
        assert not res.errors, res.errors
        assert _lints(res) == ["host_sync"], [f.fingerprint
                                              for f in res.findings]
        assert any(f.key == "pure_callback" for f in res.findings)

    def test_hoisted_scatter_caught_by_collective_placement(self, mesh8):
        """gas=2 accumulation carrying FULL gradients with the
        psum_scatter AFTER the scan — contrary to the declared explicit
        mode whose invariant is the in-scan scatter (the carry only ever
        holds 1/dp shards)."""
        n = 1024

        def per_rank(w, xs):
            def accum(g, x):
                return g + w * x.sum(), None
            g, _ = lax.scan(accum, jnp.zeros((n,), jnp.float32), xs)
            return lax.psum_scatter(g, "data", scatter_dimension=0,
                                    tiled=True)

        fn = comm.shard_map(per_rank, mesh=mesh8,
                            in_specs=(P(), P(None, "data")),
                            out_specs=P("data"), check_vma=False)
        w = jnp.ones((n,), jnp.float32)
        xs = jnp.ones((2, 8, 4), jnp.float32)
        meta = {"grad_sync_path": True, "grad_sync_mode": "explicit",
                "gas": 2, "scatterable_leaf_bytes": [n * 4]}
        with mesh8:
            res = lint_jit(jax.jit(fn), w, xs, name="seeded_hoist",
                           meta=meta)
        assert not res.errors, res.errors
        assert _lints(res) == ["collective_placement"], \
            [f.fingerprint for f in res.findings]
        f = res.findings[0]
        assert f.key.startswith("rs-hoisted")
        assert f.priced and f.wire_bytes == \
            deepspeed_tpu.parallel.hlo_audit.ring_wire_bytes(
                "reduce-scatter", n * 4, 8)

    def test_scalar_tuple_allreduce_is_not_a_gradient(self):
        """The health tap's four per-leaf sums leave XLA's all-reduce
        combiner as ONE all-reduce of four f32[] — 16 B, the bytes of an
        f32[4] leaf. What the op carries decides: the scalar tuple is a
        statistic, the f32[4] is a gradient materializing unpartitioned
        and is still reported."""
        from deepspeed_tpu.analysis.findings import LintContext
        from deepspeed_tpu.analysis.passes import \
            collective_placement_pass
        from deepspeed_tpu.parallel.hlo_audit import (CollectiveOp,
                                                      CommAudit)

        def allreduce(shapes, op_name):
            return CollectiveOp(
                kind="all-reduce", name="x", computation="", out_bytes=16,
                in_bytes=16, out_shapes=shapes, in_shapes=shapes,
                group_size=4, num_groups=2, source_target_pairs=None,
                op_name=op_name)

        tap = allreduce(["f32[]"] * 4,
                        "jit(train_step)/health_tap/reduce_sum")
        grad = allreduce(["f32[4]"], "jit(train_step)/fwd_bwd/psum")
        meta = {"grad_sync_path": True, "grad_sync_mode": "explicit",
                "gas": 2, "scatterable_leaf_bytes": [16], "dp": 4}
        ctx = LintContext(name="train_step", jaxpr=None,
                          donated_invars=(), in_avals=(), hlo_text="",
                          audit=CommAudit([tap, grad]), meta=meta)
        assert [f.key for f in collective_placement_pass(ctx)] == \
            ["grad-allreduce:f32[4]"]

    def test_allreduce_trapped_in_gas_scan_caught_dense(self, mesh8):
        """Dense mode's misplacement: the gradient all-reduce INSIDE the
        gas=2 accumulation scan pays gas x the wire it needs (accumulate
        locally, reduce once) — the else-branch of collective_placement,
        reachable on dense engines now that _lint_path_meta populates
        scatterable_leaf_bytes for stage < 2 too."""
        n = 512

        def per_rank(w, xs):
            def accum(g, x):
                gi = lax.psum(w * x.sum(), "data")   # per-micro-step sync
                return g + gi, None
            g, _ = lax.scan(accum, jnp.zeros((n,), jnp.float32), xs)
            return g

        fn = comm.shard_map(per_rank, mesh=mesh8,
                            in_specs=(P(), P(None, "data")),
                            out_specs=P(), check_vma=False)
        w = jnp.ones((n,), jnp.float32)
        xs = jnp.ones((2, 8, 4), jnp.float32)
        meta = {"grad_sync_path": True, "grad_sync_mode": "none",
                "gas": 2, "scatterable_leaf_bytes": [n * 4]}
        with mesh8:
            res = lint_jit(jax.jit(fn), w, xs, name="seeded_trapped",
                           meta=meta)
        assert not res.errors, res.errors
        assert _lints(res) == ["collective_placement"], \
            [f.fingerprint for f in res.findings]
        f = res.findings[0]
        assert f.key.startswith("ar-in-scan") and f.in_loop
        assert f.wire_bytes == 2 * \
            deepspeed_tpu.parallel.hlo_audit.ring_wire_bytes(
                "all-reduce", n * 4, 8)            # gas x per-trip wire

    def test_dense_engine_meta_exposes_grad_payloads(self, tmp_path):
        """Stage-0 dp=8 engines must hand the pass their grad leaf sizes
        (dense all-reduce payloads) — else the placement checks are
        unreachable exactly where the trapped-in-scan defect lives."""
        engine = _engine(tmp_path, "dense")
        meta = engine._lint_path_meta("train_step")
        assert meta["zero_stage"] < 2 and meta["dp"] == 8
        sizes = {int(l.size) * 4 for l in
                 jax.tree_util.tree_leaves(engine.state.params)}
        assert sizes <= set(meta["scatterable_leaf_bytes"])

    def test_grad_allreduce_under_declared_sharding_caught(self, mesh8):
        """The GSPMD declarative fallback, synthesized: a declared
        dp-sharded gradient this backend lowers to all-reduce + slice.
        The matmul matters — grad(w) sums over the dp-sharded batch, so
        the sync MUST move gradient-sized payload (an elementwise loss
        shards away and emits nothing)."""
        d = 16
        w_sh = NamedSharding(mesh8, P("data"))
        x_sh = NamedSharding(mesh8, P("data"))

        def probe(w, x):
            g = jax.grad(lambda w_, x_: jnp.mean((x_ @ w_) ** 2))(w, x)
            return lax.with_sharding_constraint(g, w_sh)

        w = jax.ShapeDtypeStruct((d, d), jnp.float32,
                                 sharding=NamedSharding(mesh8, P()))
        x = jax.ShapeDtypeStruct((d, d), jnp.float32, sharding=x_sh)
        meta = {"grad_sync_path": True, "grad_sync_mode": "declarative",
                "gas": 1, "scatterable_leaf_bytes": [d * d * 4]}
        res = lint_jit(jax.jit(probe), w, x, name="seeded_regression",
                       meta=meta)
        assert not res.errors, res.errors
        by_lint = {f.lint: f for f in res.findings}
        # This backend regresses the declaration (the hlo_audit probe is
        # part of tier-1); if a future backend honors it, the program has
        # a legal reduce-scatter and nothing may fire.
        from deepspeed_tpu.parallel import hlo_audit
        lowering = hlo_audit.zero2_grad_sync_lowering(mesh8, "data")
        if lowering == "all-reduce":
            assert "collective_placement" in by_lint
            assert by_lint["collective_placement"].key.startswith(
                "grad-allreduce")
        else:                      # pragma: no cover - honest backend
            assert "collective_placement" not in by_lint


# --------------------------------------------------------------------- #
# Clean engine paths: zero unwaived findings, zero added fences
# --------------------------------------------------------------------- #
class TestCleanEnginePaths:
    def test_zero2_engine_clean_and_fence_free(self, tmp_path):
        engine = _engine(tmp_path, "z2",
                         zero_optimization={"stage": 2})
        for i in range(2):
            engine.train_batch(batch=random_batch(n=16, seed=i))
        before = timer_mod.device_sync_count()
        rep = engine.lint_audit(waivers=load_waivers(WAIVER_FILE))
        assert timer_mod.device_sync_count() == before, \
            "the lint audit must be pure host work"
        assert not rep.errors, rep.errors
        assert rep.unwaived == [], [f.fingerprint for f in rep.unwaived]
        # The fused-chunk materialization finding is GONE, not waived:
        # the V-interleaved shard-local chunk layout keeps every flat
        # buffer dp-sharded through the kernels (ops/fused_update
        # docstring), so no full-chunk transient exists to flag.
        assert not any(f.lint == "materialization" for f, _ in rep.waived)
        assert not any(f.lint == "materialization" for f in rep.findings)

    def test_offload_engine_clean_and_fence_free(self, tmp_path):
        engine = _engine(tmp_path, "off",
                         zero_optimization={"stage": 2,
                                            "cpu_offload": True},
                         optimizer={"type": "Adam",
                                    "params": {"lr": 1e-2}})
        for i in range(2):
            engine.train_batch(batch=random_batch(n=16, seed=i))
        before = timer_mod.device_sync_count()
        rep = engine.lint_audit(waivers=load_waivers(WAIVER_FILE))
        assert timer_mod.device_sync_count() == before
        assert not rep.errors, rep.errors
        assert rep.unwaived == [], [f.fingerprint for f in rep.unwaived]
        # Since ISSUE 11 the offload grad pass takes the explicit
        # psum_scatter builder, so the declarative-regression finding
        # (and the waiver that covered it — the last one) is GONE, not
        # waived: the offload engine audits completely clean.
        assert rep.waived == []
        assert not any(f.lint == "collective_placement"
                       for f in rep.findings), \
            [f.fingerprint for f in rep.findings]

    def test_main_step_donations_all_aliased(self, tmp_path):
        """Regression for the donated-but-unaliased finding the linter
        surfaced on the ZeRO train step: without declared out_shardings,
        jax paired donated params to same-aval dp-sharded moments and the
        partitioner dropped the aliases — every param-sized buffer leaked
        one step of lifetime. The fix (state+metrics out_shardings on all
        donating step programs) must keep the donation pass silent."""
        engine = _engine(tmp_path, "don",
                         zero_optimization={"stage": 2},
                         optimizer={"type": "Adam",
                                    "params": {"lr": 1e-2,
                                               "fused": False}})
        engine.train_batch(batch=random_batch(n=16))
        rep = engine.lint_audit()
        assert not any(f.lint == "donation" for f in rep.findings), \
            [f.summary for f in rep.findings]
        # And structurally: every donated entry param is in the compiled
        # alias table.
        fn, a, kw = engine.telemetry.sentinel.registered_paths()[
            "train_step"]
        hlo = fn.lower(*a, **kw).compile().as_text()
        aliased = set(hlo_text.input_output_alias_params(hlo))
        n_params = len(hlo_text.entry_parameter_shapes(hlo))
        # 19 state leaves donated; batch + rng are not.
        assert len(aliased) == n_params - 2

    def test_trio_grad_step_uses_guaranteed_reduce_scatter(self, tmp_path):
        """Regression for the second true positive: the trio's
        ``grad_step`` declared sharded out_shardings, which this
        backend's GSPMD lowers to a full all-reduce + slice. Resolved-
        explicit engines now route it through the psum_scatter path:
        the compiled program must reduce-scatter and never all-reduce a
        gradient-sized payload."""
        engine = _engine(tmp_path, "trio",
                         zero_optimization={"stage": 2},
                         optimizer={"type": "Adam",
                                    "params": {"lr": 1e-2,
                                               "fused": False}})
        assert engine._grad_sync_mode == "explicit"
        batch = random_batch(n=16)
        engine.forward(batch)
        engine.backward()
        engine.step()
        rep = engine.lint_audit(waivers=load_waivers(WAIVER_FILE))
        assert rep.unwaived == [], [f.fingerprint for f in rep.unwaived]
        assert {"grad_step", "apply_grads"} <= \
            {p.name for p in rep.paths}
        fn, a, kw = engine.telemetry.sentinel.registered_paths()[
            "grad_step"]
        from deepspeed_tpu.parallel import hlo_audit
        audit = hlo_audit.audit_jit(fn, *a, **kw)
        assert audit.of_kind("reduce-scatter"), audit.summary()

    def test_trio_explicit_matches_declarative_values(self, tmp_path):
        """The explicit trio backward is numerically the declarative one:
        same loss, grads within f32 ulp (collective reduction order is
        the only difference — the PR-3 cross-program precedent)."""
        engines = {}
        for mode in ("declarative", "explicit"):
            engines[mode] = _engine(
                tmp_path, f"trio_{mode}", seed=3,
                zero_optimization={"stage": 2, "grad_sync": mode},
                optimizer={"type": "Adam",
                           "params": {"lr": 1e-2, "fused": False}})
        batch = random_batch(n=16, seed=5)
        losses, grads = {}, {}
        for mode, e in engines.items():
            losses[mode] = float(e.forward(batch))
            grads[mode] = jax.device_get(e._stashed_grads)
        assert losses["declarative"] == pytest.approx(
            losses["explicit"], rel=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(grads["declarative"]),
                        jax.tree_util.tree_leaves(grads["explicit"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-6)


# --------------------------------------------------------------------- #
# Degraded-mapping fallback: count-only judgment must still report
# --------------------------------------------------------------------- #
class TestDonationFallback:
    def test_unattributable_mapping_still_finds_unaliased(self):
        """When the kept-parameter mapping is unavailable (len(kept) !=
        len(param_shapes): exotic backend / API drift), the pass judges
        by count only — and its unpriced (0-byte) finding must not be
        swallowed by the default donation_floor_bytes=0 guard."""
        from deepspeed_tpu.analysis.findings import LintContext
        from deepspeed_tpu.analysis.passes import donation_pass
        synth = ("HloModule m, entry_computation_layout="
                 "{(f32[4]{0}, f32[4]{0}, f32[4]{0})->f32[4]{0}}\n")
        ctx = LintContext(name="degraded", jaxpr=None,
                          donated_invars=(True, True), in_avals=(),
                          hlo_text=synth, audit=None)   # kept=[0,1] vs 3
        out = donation_pass(ctx)
        assert len(out) == 1 and out[0].lint == "donation"
        assert out[0].count == 2 and out[0].bytes == 0
        assert "unattributable" in str(out[0].details["unaliased_params"])

    def test_attributable_zero_bytes_stays_suppressed(self):
        """The floor guard still applies when bytes ARE attributable."""
        from deepspeed_tpu.analysis.findings import LintContext
        from deepspeed_tpu.analysis.passes import donation_pass
        synth = ("HloModule m, entry_computation_layout="
                 "{(f32[0]{0}, f32[4]{0})->f32[4]{0}}\n")
        ctx = LintContext(name="zero", jaxpr=None,
                          donated_invars=(True, False), in_avals=(),
                          hlo_text=synth, audit=None)
        assert donation_pass(ctx) == []

    def test_degraded_fallback_ignores_dropped_donated_args(self):
        """A donated arg jit DROPPED (keep_unused=False) is trivially
        honored and must not inflate the count-only expectation: with
        kept_var_idx in hand the kept donated args are counted exactly,
        so one aliased kept donation + one dropped donation is clean —
        not a spurious unwaivable finding."""
        from deepspeed_tpu.analysis.findings import LintContext
        from deepspeed_tpu.analysis.passes import donation_pass
        # 2 entry params vs len(kept)=1 -> mapping unattributable.
        synth = ("HloModule m, entry_computation_layout="
                 "{(f32[4]{0}, f32[4]{0})->f32[4]{0}}, "
                 "input_output_alias={ {}: (0, {}) }\n")
        ctx = LintContext(name="dropped", jaxpr=None,
                          donated_invars=(True, True), in_avals=(),
                          hlo_text=synth, audit=None, kept_var_idx=(0,))
        assert donation_pass(ctx) == []
        # The same kept mapping with NO alias entry still reports the
        # one genuinely kept-but-unaliased donation.
        bare = synth.replace(", input_output_alias={ {}: (0, {}) }", "")
        ctx = LintContext(name="dropped", jaxpr=None,
                          donated_invars=(True, True), in_avals=(),
                          hlo_text=bare, audit=None, kept_var_idx=(0,))
        out = donation_pass(ctx)
        assert len(out) == 1 and out[0].count == 1

    def test_degraded_fallback_without_kept_mapping_bounds_drops(self):
        """No kept_var_idx at all: at most n_args - n_entry_params
        inputs were dropped, so 2 donated args against 1 entry param and
        1 alias cannot prove an unhonored donation -> clean."""
        from deepspeed_tpu.analysis.findings import LintContext
        from deepspeed_tpu.analysis.passes import donation_pass
        synth = ("HloModule m, entry_computation_layout="
                 "{(f32[4]{0})->f32[4]{0}}, "
                 "input_output_alias={ {}: (0, {}) }\n")
        ctx = LintContext(name="bounded", jaxpr=None,
                          donated_invars=(True, True), in_avals=(),
                          hlo_text=synth, audit=None)
        assert donation_pass(ctx) == []


# --------------------------------------------------------------------- #
# Waiver machinery
# --------------------------------------------------------------------- #
class TestWaivers:
    def _finding(self, key="f32[131076]", lint="materialization",
                 path="train_step"):
        return LintFinding(lint=lint, path=path, key=key, summary="s")

    def test_glob_is_bracket_safe(self):
        """HLO shapes contain ``[...]`` — fnmatch character classes would
        swallow them; only ``*`` may be a wildcard."""
        w = Waiver(match="materialization:train_step:f32[131076]")
        assert w.matches(self._finding())
        assert not w.matches(self._finding(key="f32[1]"))
        star = Waiver(match="materialization:*:f32[131076]")
        assert star.matches(self._finding())
        assert not star.matches(self._finding(lint="donation"))

    def test_apply_waivers_splits_and_reports_stale(self):
        f1, f2 = self._finding(), self._finding(key="f32[9]",
                                                lint="dtype_flow")
        live = Waiver(match="materialization:*")
        stale = Waiver(match="host_sync:*", reason="gone")
        unwaived, waived, stales = apply_waivers([f1, f2], [live, stale])
        assert unwaived == [f2]
        assert [(f.fingerprint, w.match) for f, w in waived] == \
            [(f1.fingerprint, live.match)]
        assert stales == [stale]

    def test_load_waivers_missing_file_is_empty_baseline(self, tmp_path):
        assert load_waivers(str(tmp_path / "nope.json")) == []

    def test_repo_waiver_file_loads_with_roadmap_pointers(self):
        assert os.path.isfile(WAIVER_FILE), \
            "tools/lint_waivers.json must exist"
        waivers = load_waivers(WAIVER_FILE)
        # The baseline is EMPTY since the ZeRO-3 round retired the last
        # waiver (the offload grad pass now takes the explicit
        # psum_scatter builder); any future waiver needs a ROADMAP
        # pointer (waivers are debts).
        assert all(w.roadmap for w in waivers), \
            "every waiver needs a ROADMAP pointer (waivers are debts)"


# --------------------------------------------------------------------- #
# LINT_AUDIT.json: the recorded artifact's consistency contract
# --------------------------------------------------------------------- #
class TestLintAuditArtifact:
    @pytest.fixture(scope="class")
    def record(self):
        path = os.path.join(REPO, "LINT_AUDIT.json")
        assert os.path.exists(path), "run tools/ds_lint.py"
        return json.load(open(path))

    def test_all_pass_and_zero_fences(self, record):
        assert record["all_pass"] is True
        assert record["audit_device_fences"] == 0
        for name in ("zero1", "zero2", "zero3", "onebit", "offload",
                     "pipeline_1f1b", "serving"):
            assert record["configs"][name]["pass"] is True, name

    def test_every_finding_priced_or_explicitly_unpriced(self, record):
        for cfg in record["configs"].values():
            for f in cfg.get("findings", []):
                assert "priced" in f, f
                if f["priced"]:
                    assert isinstance(f.get("wire_bytes"), int), f
                else:
                    assert "bytes" in f, f

    def test_every_waiver_matches_a_live_finding(self, record):
        assert record["stale_waivers"] == []
        live = {f["fingerprint"] for c in record["configs"].values()
                for f in c.get("findings", [])}
        for entry in record["waived"]:
            assert entry["finding"]["fingerprint"] in live

    def test_ds_report_prints_lint_summary(self, record, capsys):
        from deepspeed_tpu import env_report
        lines = env_report.lint_report(
            [], path=os.path.join(REPO, "LINT_AUDIT.json"))
        assert lines and "static lint" in lines[-1]
        assert "waived" in lines[-1] and "newest" in lines[-1]

    def test_ds_report_silent_without_artifact(self, tmp_path,
                                               monkeypatch):
        from deepspeed_tpu import env_report
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("DS_LINT_AUDIT", raising=False)
        monkeypatch.setattr(env_report, "find_lint_audit",
                            lambda path=None: "")
        assert env_report.lint_report([]) == []

    def test_explicit_missing_audit_never_falls_back(self, tmp_path,
                                                     monkeypatch):
        """An explicitly requested artifact ($DS_LINT_AUDIT or the path
        arg) that does not exist must be reported missing — never
        silently replaced by a stale fallback from cwd/repo root."""
        from deepspeed_tpu import env_report
        stale = tmp_path / "LINT_AUDIT.json"
        stale.write_text(json.dumps({"all_pass": True, "configs": {},
                                     "waived": []}))
        monkeypatch.chdir(tmp_path)   # stale artifact sits in cwd
        missing = str(tmp_path / "fresh" / "LINT_AUDIT.json")
        monkeypatch.delenv("DS_LINT_AUDIT", raising=False)
        assert env_report.find_lint_audit(missing) == ""
        lines = env_report.lint_report([], path=missing)
        assert lines == [f"static lint: requested audit missing: {missing}"]
        monkeypatch.setenv("DS_LINT_AUDIT", missing)
        assert env_report.find_lint_audit() == ""
        lines = env_report.lint_report([])
        assert lines == [f"static lint: requested audit missing: {missing}"]
        # The unrequested fallback chain still finds the cwd artifact.
        monkeypatch.delenv("DS_LINT_AUDIT", raising=False)
        assert env_report.find_lint_audit() == str(stale)

    @pytest.mark.slow
    def test_configs_subset_does_not_fail_on_foreign_waivers(self,
                                                             tmp_path):
        """--configs zero1 must not read the offload waiver as stale
        (findings.apply_waivers contract: a waiver for config B is not
        stale while auditing config A) nor overwrite a failing artifact."""
        import subprocess
        out = str(tmp_path / "subset.json")
        r = subprocess.run(
            [os.sys.executable, os.path.join(REPO, "tools", "ds_lint.py"),
             "--configs", "zero1", "--check", "--out", out],
            capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        rec = json.load(open(out))
        assert rec["subset"] is True
        assert rec["stale_waivers"] == []
        assert rec["all_pass"] is True


# --------------------------------------------------------------------- #
# Registry handoff (monitor/recompile.py)
# --------------------------------------------------------------------- #
class TestRegistryHandoff:
    def test_registered_paths_after_one_step(self, tmp_path):
        engine = _engine(tmp_path, "reg")
        engine.train_batch(batch=random_batch(n=16))
        reg = engine.telemetry.sentinel.registered_paths()
        assert "train_step" in reg
        fn, a_args, a_kwargs = reg["train_step"]
        assert hasattr(fn, "lower")
        assert isinstance(a_args, tuple) and isinstance(a_kwargs, dict)
        # The recorded signature is abstract: re-lowering it must not
        # touch device buffers.
        before = timer_mod.device_sync_count()
        fn.lower(*a_args, **a_kwargs)
        assert timer_mod.device_sync_count() == before

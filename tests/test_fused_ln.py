"""Fused elementwise Pallas kernels (ops/fused_elementwise) vs the jnp
reference chain — the parity contract for the reference's fused
transformer kernels (normalize_kernels.cu / gelu_kernels.cu class).

Numerics tiers (documented bounds, PR-1 precedent):

- fp32 tensors: fused and unfused agree to a few f32 ulp — both compute
  identical fp32 expressions; the residue is cross-program reduction
  association (the same limit PR 1 documented for FMA contraction).
- bf16 tensors: within ~2 bf16 ulp of each other. The fused path rounds
  ONCE at the kernel output where the unfused chain rounds per op, so
  the fused value is the more accurate one; gradients through deep
  bf16 chains compound per-op rounding and are compared at bf16
  tolerance against the same reference.
- The fused residual sum ``s = x + delta`` is BIT-equal to the unfused
  add (round(f32 sum) IS the bf16 add).

Engine tier: gpt2-tiny on the 8-device CPU mesh (interpret-mode Pallas)
— train-step parity kernels on/off, checkpoint resume-compatibility
across the knob, serving recompile-freedom, and the materialization +
dtype_flow lint passes clean with kernels ON.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capability import fused_elementwise_skip_reason
from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_apply, gpt2_init,
                                       gpt2_loss_fn)
from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              init_block_params,
                                              layer_norm,
                                              transformer_block)
from deepspeed_tpu.ops.fused_elementwise import (bias_gelu,
                                                 fused_elementwise_enabled,
                                                 fused_layer_norm,
                                                 fused_residual_layer_norm)
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

pytestmark = pytest.mark.skipif(
    fused_elementwise_skip_reason() is not None,
    reason=fused_elementwise_skip_reason() or "")

F32_RTOL, F32_ATOL = 1e-5, 1e-6
BF16_RTOL, BF16_ATOL = 0.05, 0.05      # ~2 bf16 ulp at unit magnitude


def _tols(dtype):
    return (BF16_RTOL, BF16_ATOL) if dtype == jnp.bfloat16 \
        else (F32_RTOL, F32_ATOL)


def _rand(shape, seed, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.standard_normal(shape), jnp.float32).astype(dtype)


def _close(a, b, dtype, scale=1.0):
    rtol, atol = _tols(dtype)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=rtol * scale, atol=atol * scale)


# --------------------------------------------------------------------- #
# Kernel tier
# --------------------------------------------------------------------- #
class TestLayerNormParity:
    # H=100 exercises the lane-pad mask; 1600 the multi-of-128-but-not-
    # power-of-two width of gpt2-xl.
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("H", [128, 100, 1600])
    def test_fwd_parity(self, dtype, H):
        x = _rand((2, 17, H), 0, dtype)
        sc, bi = _rand((H,), 1), _rand((H,), 2)
        y = jax.jit(lambda *a: fused_layer_norm(*a, 1e-5))(x, sc, bi)
        assert y.dtype == dtype and y.shape == x.shape
        _close(y, layer_norm(x, sc, bi, 1e-5), dtype)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("H", [128, 100])
    def test_bwd_parity(self, dtype, H):
        x = _rand((3, 9, H), 3, dtype)
        sc, bi = _rand((H,), 4), _rand((H,), 5)

        def loss(fn):
            def run(x, sc, bi):
                return jnp.sum(fn(x, sc, bi).astype(jnp.float32) ** 2)
            return jax.grad(run, argnums=(0, 1, 2))(x, sc, bi)

        gf = loss(lambda x, s, b: fused_layer_norm(x, s, b, 1e-5))
        gr = loss(lambda x, s, b: layer_norm(x, s, b, 1e-5))
        for a, b in zip(gf, gr):
            # dscale/dbias sum over all rows: scale tolerance with the
            # row count (reduction of per-element rounding residue).
            _close(a, b, dtype, scale=4.0)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_residual_sum_bit_parity(self, dtype):
        """The fused s = x + delta is BITWISE the unfused add: one f32
        sum rounded once IS the dtype's add."""
        H = 256
        x, d = _rand((4, 8, H), 6, dtype), _rand((4, 8, H), 7, dtype)
        sc, bi = _rand((H,), 8), _rand((H,), 9)
        s, y = jax.jit(lambda *a: fused_residual_layer_norm(*a, 1e-5))(
            x, d, sc, bi)
        np.testing.assert_array_equal(
            np.asarray(s, np.float32), np.asarray(x + d, np.float32))
        _close(y, layer_norm(x + d, sc, bi, 1e-5), dtype)

    def test_residual_bwd_carries_both_cotangents(self):
        """grad flows through BOTH outputs (s continues the residual
        stream, y feeds the sublayer) and dx == ddelta."""
        H = 128
        x, d = _rand((2, 4, H), 10), _rand((2, 4, H), 11)
        sc, bi = _rand((H,), 12), _rand((H,), 13)

        def fused(x, d, sc, bi):
            s, y = fused_residual_layer_norm(x, d, sc, bi, 1e-5)
            return jnp.sum(y ** 2) + jnp.sum(jnp.sin(s))

        def ref(x, d, sc, bi):
            s = x + d
            return jnp.sum(layer_norm(s, sc, bi, 1e-5) ** 2) + \
                jnp.sum(jnp.sin(s))

        gf = jax.grad(fused, argnums=(0, 1, 2, 3))(x, d, sc, bi)
        gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, d, sc, bi)
        for a, b in zip(gf, gr):
            _close(a, b, jnp.float32, scale=4.0)
        np.testing.assert_array_equal(np.asarray(gf[0]), np.asarray(gf[1]))


class TestBiasGelu:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("exact", [False, True])
    def test_fwd_parity(self, dtype, exact):
        F = 512
        y, b = _rand((33, F), 20, dtype), _rand((F,), 21)
        out = jax.jit(lambda y, b: bias_gelu(y, b, exact))(y, b)
        ref = jax.nn.gelu(y + b.astype(y.dtype), approximate=not exact)
        assert out.dtype == dtype
        _close(out, ref, dtype)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_bwd_parity(self, dtype):
        F = 384
        y, b = _rand((16, F), 22, dtype), _rand((F,), 23)

        def loss(fn):
            def run(y, b):
                return jnp.sum(fn(y, b).astype(jnp.float32) ** 2)
            return jax.grad(run, argnums=(0, 1))(y, b)

        gf = loss(lambda y, b: bias_gelu(y, b))
        gr = loss(lambda y, b: jax.nn.gelu(y + b.astype(y.dtype),
                                           approximate=True))
        _close(gf[0], gr[0], dtype, scale=4.0)
        # dbias sums dz over ALL rows — bf16 per-op rounding of the
        # unfused chain accumulates linearly with the row count.
        _close(gf[1], gr[1], dtype, scale=16.0)
        # ... and is summed from the fp32 dz, whatever dy rounds to: a
        # sum of bf16-rounded terms is off by ~1e-3 of it.
        from deepspeed_tpu.ops.fused_elementwise import _dgelu_f32
        z = (y.astype(jnp.float32) + b).astype(dtype).astype(jnp.float32)
        out = bias_gelu(y, b).astype(jnp.float32)
        dz = (2.0 * out) * _dgelu_f32(z, False)
        np.testing.assert_allclose(np.asarray(gf[1]),
                                   np.asarray(jnp.sum(dz, axis=0)),
                                   rtol=1e-5, atol=1e-5)


class TestKnobResolution:
    def test_forced_values(self):
        assert fused_elementwise_enabled(True) is True
        assert fused_elementwise_enabled(False) is False

    def test_auto_follows_backend_and_env(self, monkeypatch):
        monkeypatch.delenv("DS_FUSED_ELEMENTWISE", raising=False)
        expect = jax.default_backend() == "tpu"
        assert fused_elementwise_enabled("auto") is expect
        monkeypatch.setenv("DS_FUSED_ELEMENTWISE", "1")
        assert fused_elementwise_enabled("auto") is True
        monkeypatch.setenv("DS_FUSED_ELEMENTWISE", "0")
        assert fused_elementwise_enabled("auto") is False
        # forced values beat the env override
        monkeypatch.setenv("DS_FUSED_ELEMENTWISE", "1")
        assert fused_elementwise_enabled(False) is False


# --------------------------------------------------------------------- #
# Block / model tier
# --------------------------------------------------------------------- #
def _block_cfg(**over):
    base = dict(hidden_size=128, num_heads=4, num_layers=2,
                max_seq_length=32, vocab_size=512, hidden_dropout=0.0,
                attn_dropout=0.0, dtype=jnp.float32, causal=True)
    base.update(over)
    return TransformerConfig(**base)


class TestBlockParity:
    @pytest.mark.parametrize("pre_ln", [True, False])
    def test_block_fwd_bwd_parity_fp32(self, pre_ln):
        cfg_on = _block_cfg(pre_layer_norm=pre_ln, fused_kernels=True)
        cfg_off = dataclasses.replace(cfg_on, fused_kernels=False)
        params = jax.tree_util.tree_map(
            lambda t: t[0], init_block_params(jax.random.PRNGKey(0),
                                              cfg_on, num_layers=1))
        x = _rand((2, 16, 128), 30)

        def run(cfg):
            def loss(p, x):
                return jnp.sum(transformer_block(p, x, cfg) ** 2)
            v, g = jax.value_and_grad(loss)(params, x)
            return v, g

        v_on, g_on = run(cfg_on)
        v_off, g_off = run(cfg_off)
        np.testing.assert_allclose(float(v_on), float(v_off), rtol=1e-5)
        for k in g_on:
            _close(g_on[k], g_off[k], jnp.float32, scale=10.0)

    def test_gpt2_apply_parity_bf16(self):
        cfg_off = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"],
                                      hidden_dropout=0.0, attn_dropout=0.0,
                                      fused_kernels=False)
        cfg_on = dataclasses.replace(cfg_off, fused_kernels=True)
        params = gpt2_init(jax.random.PRNGKey(0), cfg_off)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg_off.vocab_size, (2, 33)), jnp.int32)
        lo = jax.jit(lambda p, t: gpt2_apply(p, t, cfg_off))(params, toks)
        ln = jax.jit(lambda p, t: gpt2_apply(p, t, cfg_on))(params, toks)
        _close(ln, lo, jnp.bfloat16)


# --------------------------------------------------------------------- #
# Engine tier — 8-device CPU mesh
# --------------------------------------------------------------------- #
def _gpt2_cfg(fused, dtype=jnp.float32):
    return dataclasses.replace(
        GPT2_CONFIGS["gpt2-tiny"], hidden_dropout=0.0, attn_dropout=0.0,
        dtype=dtype, fused_kernels=fused)


def _ds_cfg(**over):
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3,
                                                  "fused": True}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10 ** 9,
    }
    cfg.update(over)
    return cfg


def _token_batch(i, cfg, n=8):
    r = np.random.default_rng(i)
    return jnp.asarray(r.integers(0, cfg.vocab_size, (n, 17)), jnp.int32)


def _train(model_cfg, steps=4, ds_over=None, seed=0):
    eng = DeepSpeedEngine(model=gpt2_loss_fn(model_cfg),
                          model_params=gpt2_init(jax.random.PRNGKey(seed),
                                                 model_cfg),
                          config=_ds_cfg(**(ds_over or {})),
                          mesh=build_mesh())
    losses = [float(jax.device_get(eng.train_batch(
        _token_batch(i, model_cfg)))) for i in range(steps)]
    return eng, losses


class TestEngineTier:
    def test_train_step_parity_kernels_on_off(self):
        """fp32 gpt2-tiny under ZeRO-2 + clipping + the one-pass fused
        optimizer on the dp=8 mesh: fused-kernel and reference
        trajectories agree to f32 accumulation tolerance."""
        eng_on, l_on = _train(_gpt2_cfg(True))
        eng_off, l_off = _train(_gpt2_cfg(False))
        np.testing.assert_allclose(l_on, l_off, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(
                eng_on.state.params["ln_f_scale"]), np.float32),
            np.asarray(jax.device_get(
                eng_off.state.params["ln_f_scale"]), np.float32),
            rtol=1e-4, atol=1e-5)

    def test_checkpoint_roundtrip_across_knob(self, tmp_path):
        """Runs with kernels on and off are RESUME-COMPATIBLE: the knob
        changes the program, not the state (params, moments, loss-scale
        machinery all identical structures)."""
        eng_on, _ = _train(_gpt2_cfg(True), steps=3)
        eng_on.save_checkpoint(str(tmp_path), tag="k3")
        eng_off, _ = _train(_gpt2_cfg(False), steps=1, seed=1)
        eng_off.load_checkpoint(str(tmp_path), tag="k3")
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(eng_on.state.opt_state.m[0])),
            np.asarray(jax.device_get(eng_off.state.opt_state.m[0])))
        cfg_on, cfg_off = _gpt2_cfg(True), _gpt2_cfg(False)
        l_on = float(jax.device_get(eng_on.train_batch(
            _token_batch(50, cfg_on))))
        l_off = float(jax.device_get(eng_off.train_batch(
            _token_batch(50, cfg_off))))
        np.testing.assert_allclose(l_on, l_off, rtol=2e-4, atol=2e-5)

    def test_lint_clean_with_kernels_on(self, tmp_path):
        """The acceptance gate's lint half: materialization + dtype_flow
        CLEAN (zero unwaived findings) on the dp=8 ZeRO-2 engine with
        the fused kernels AND the one-pass fused optimizer enabled —
        the kernels run inside the explicit shard_map gradient path
        where every operand is already device-local, so no activation
        gather materializes."""
        cfg = _gpt2_cfg(True)
        eng = DeepSpeedEngine(
            model=gpt2_loss_fn(cfg),
            model_params=gpt2_init(jax.random.PRNGKey(0), cfg),
            config=_ds_cfg(telemetry={
                "enabled": True, "output_path": str(tmp_path),
                "job_name": "fk", "report_steps": 10 ** 9}),
            mesh=build_mesh())
        for i in range(2):
            eng.train_batch(_token_batch(i, cfg))
        rep = eng.lint_audit(passes=("materialization", "dtype_flow"))
        assert not rep.errors, rep.errors
        assert rep.unwaived == [], [f.fingerprint for f in rep.unwaived]
        eng.telemetry.close()


class TestServingRecompiles:
    def test_zero_extra_recompiles_with_fused_ln(self, tmp_path):
        """The serving satellite: the decode/prefill paths pick up the
        fused LayerNorm through the SAME cfg-static dispatch as
        training — an open-loop stream under fail_on_recompile compiles
        each path once, kernels on."""
        from deepspeed_tpu.inference import (InferenceEngine,
                                             synthetic_requests)
        cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"],
                                  fused_kernels=True)
        eng = InferenceEngine(
            cfg, gpt2_init(jax.random.PRNGKey(1), cfg),
            config={
                "inference": {"max_slots": 8, "max_seq_len": 32,
                              "prefill_chunk": 8},
                "telemetry": {"enabled": True,
                              "output_path": str(tmp_path),
                              "job_name": "serve_fk",
                              "report_steps": 10 ** 6,
                              "fail_on_recompile": True}})
        reqs = synthetic_requests(8, prompt_len=(4, 12), max_new_tokens=5,
                                  vocab_size=cfg.vocab_size, seed=5)
        report = eng.serve(reqs)
        assert report["completed"] == 8 and report["unfinished"] == 0
        assert report["recompiles"] == 0
        assert eng.telemetry.recompile_count == 0
        eng.close()

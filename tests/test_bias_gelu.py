"""The FFN up-projection's bias + GELU is one ``jax.numpy`` expression for
every caller (``ops.fused_elementwise.bias_gelu`` through
``models.transformer.gelu_dense_fn``, PR 51): the two Pallas kernels that
stood between the FFN's GEMMs are gone, whatever ``fused_kernels`` says.

What this file holds it to:

- no program traces a GELU kernel, while the LayerNorm kernels still follow
  the switch (the train step of a tiny GPT-2, as the engine registers it);
- under ``remat_policy="dots_flash"`` a block saves exactly one
  ``[rows, F]`` tensor, the GEMM's output in the compute dtype — the guard
  for ``train_peak_hbm_gb`` that runs on a CPU — and without remat nothing
  ``F`` wide is kept in float32;
- the values are the deleted kernel's, on the input and outputs recorded
  from it at PR 50 (``tests/data/bias_gelu_kernel_pr50.npz``, written by
  the parent's ``fused_bias_gelu`` in interpret mode): the same precision,
  not the per-operation bf16 chain;
- serving's ``mlp`` at a decode iteration's few rows is the training
  block's.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_init, gpt2_loss_fn
from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              _remat_policy,
                                              init_block_params,
                                              transformer_block)
from deepspeed_tpu.ops.fused_elementwise import bias_gelu
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

RECORDED = np.load(os.path.join(os.path.dirname(__file__), "data",
                                "bias_gelu_kernel_pr50.npz"))
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# One step of the storage type at the value's size, which is what "the
# same precision" means for a tensor rounded once: bf16 keeps 8 bits.
ULP = {"f32": 2.0 ** -23, "bf16": 2.0 ** -8}
_KERNEL = re.compile(r"\b(_\w+_kernel)\b")


def _within_ulps(got, want, dname, ulps, floor=1.0):
    """``got`` within ``ulps`` steps of ``want``, a step taken at the
    larger of the value's size and ``floor`` (a sum's step is its
    terms')."""
    got = np.asarray(got.astype(jnp.float32))
    step = ULP[dname] * np.maximum(np.abs(want), floor)
    worst = float(np.max(np.abs(got - want) / step))
    assert worst <= ulps, f"{worst:.2f} steps apart, {ulps} allowed"


# --------------------------------------------------------------------- #
# (a) no program holds a GELU kernel
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("fused", [True, False, "auto"])
def test_train_step_holds_no_gelu_kernel(fused, tmp_path):
    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32,
                              fused_kernels=fused)
    eng = DeepSpeedEngine(
        model=gpt2_loss_fn(cfg),
        model_params=gpt2_init(jax.random.PRNGKey(0), cfg),
        config={"train_batch_size": 2,
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "steps_per_print": 10 ** 9,
                "telemetry": {"enabled": True, "output_path": str(tmp_path),
                              "job_name": "bg", "report_steps": 10 ** 9}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 17)), jnp.int32)
    eng.train_batch(tokens)
    fn, args, kwargs = eng.telemetry.sentinel.registered_paths()["train_step"]
    kernels = set(_KERNEL.findall(str(jax.make_jaxpr(fn)(*args, **kwargs))))
    eng.telemetry.close()
    assert not any("gelu" in k for k in kernels), kernels
    # The switch still means something: LayerNorm follows it.
    assert ({"_ln_fwd_kernel", "_ln_bwd_kernel"} <= kernels) is (fused is True)
    assert "gelu_fwd_kernel" not in fn.lower(*args, **kwargs).as_text()


# --------------------------------------------------------------------- #
# (b) what the backward keeps of the FFN's [rows, F] tensors
# --------------------------------------------------------------------- #
def _block(dtype=jnp.bfloat16, **over):
    cfg = TransformerConfig(**{**dict(
        hidden_size=32, num_heads=2, num_layers=1, intermediate_size=128,
        max_seq_length=8, causal=True, dtype=dtype, hidden_dropout=0.0,
        attn_dropout=0.0, fused_kernels=False), **over})
    params = jax.tree_util.tree_map(
        lambda t: t[0], init_block_params(jax.random.PRNGKey(0), cfg,
                                          num_layers=1))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 8, cfg.hidden_size)), dtype)
    return cfg, params, x


def _saved_f_wide(fn, cfg, *args):
    from jax._src.ad_checkpoint import saved_residuals
    return [aval for aval, _ in saved_residuals(fn, *args)
            if aval.shape[-1:] == (cfg.ffn_size,) and aval.ndim == 3]


def test_dots_flash_saves_one_rows_by_f_tensor_a_layer():
    cfg, params, x = _block()
    block = jax.checkpoint(lambda p, x: transformer_block(p, x, cfg),
                           policy=_remat_policy("dots_flash"))
    kept = _saved_f_wide(block, cfg, params, x)
    assert [(a.shape, a.dtype) for a in kept] == \
        [((2, 8, cfg.ffn_size), jnp.bfloat16)], kept


def test_without_remat_nothing_f_wide_is_kept_in_float32():
    """The custom vjp's residual is ``(y, bias)``: beside ``y`` the
    backward keeps the GELU's output, which the next GEMM's weight
    gradient reads, both in the compute dtype.  Plain autodiff of the
    fp32 expression keeps its fp32 intermediates."""
    cfg, params, x = _block()
    kept = _saved_f_wide(lambda p, x: transformer_block(p, x, cfg), cfg,
                         params, x)
    assert len(kept) == 2 and all(a.dtype == jnp.bfloat16 for a in kept), kept


# --------------------------------------------------------------------- #
# (c) the deleted kernel's values, as recorded from the parent
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("form", ["tanh", "erf"])
@pytest.mark.parametrize("dname", ["f32", "bf16"])
def test_values_are_the_recorded_kernels(dname, form):
    dt = DTYPES[dname]
    y = jnp.asarray(RECORDED["k_y"]).astype(dt)
    g = jnp.asarray(RECORDED["k_g"]).astype(dt)
    b = jnp.asarray(RECORDED["k_bias"])
    out, vjp = jax.vjp(lambda y, b: bias_gelu(y, b, form == "erf"), y, b)
    dy, dbias = vjp(g)
    assert out.dtype == dt and dy.dtype == dt and dbias.dtype == b.dtype
    want = {k: RECORDED[f"k_{dname}_{form}_{k}"]
            for k in ("out", "dy", "dbias")}
    # Rounded once from fp32 arithmetic on both sides: at most the last
    # bf16 bit apart; in fp32 the tanh / erf of two compilations differ
    # in their last bits, 1 + tanh(u) and 1 - tanh(u)^2 carry that at
    # the size of 1, and z * du (10 at z = -4) scales it in dy.
    steps, floor = (1.0, 2.0 ** -6) if dname == "bf16" else (32.0, 1.0)
    _within_ulps(out, want["out"], dname, steps, floor)
    _within_ulps(dy, want["dy"], dname, steps, floor)
    # Summed from the fp32 dz in both: fp32 steps of a 12-term sum, also
    # where dy is bf16 (a sum of ROUNDED terms is ~2^-9 of it off).
    _within_ulps(dbias, want["dbias"], "f32", 64.0)


def test_a_bias_per_expert_sums_over_the_rows_it_was_broadcast_along():
    """``[E, 1, F]`` under ``[E, C, F]`` (the MoE FFN's einsum path):
    ``dbias`` keeps the bias's shape and is what plain autodiff of the
    expression gives."""
    from deepspeed_tpu.ops.fused_elementwise import _gelu_f32
    r = np.random.default_rng(3)
    y = jnp.asarray(r.standard_normal((3, 5, 128)), jnp.float32)
    b = jnp.asarray(r.standard_normal((3, 1, 128)), jnp.float32)

    def loss(fn):
        return jax.grad(lambda y, b: jnp.sum(fn(y, b) ** 2), (0, 1))(y, b)

    got = loss(bias_gelu)
    want = loss(lambda y, b: _gelu_f32(y + b, False))
    assert got[1].shape == b.shape
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dname", ["f32", "bf16"])
def test_block_is_the_recorded_kernel_blocks(dname):
    """One block forward + gradients, fused_kernels=True (LayerNorm
    through the same kernels as the recording, so the GELU is the only
    thing that changed) against the parent's."""
    dt = DTYPES[dname]
    cfg, _, _ = _block(dtype=dt, fused_kernels=True)
    params = {k[len("b_param_"):]: jnp.asarray(v)
              for k, v in RECORDED.items() if k.startswith("b_param_")}
    x = jnp.asarray(RECORDED["b_x"]).astype(dt)

    def loss(p, x):
        o = transformer_block(p, x, cfg)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o

    (_, out), (gp, gx) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(params, x)
    # A last-bit difference in one GELU output travels through a GEMM:
    # a few steps at the size of the tensor, not the per-op chain's 2^-6.
    steps = 4.0 if dname == "bf16" else 64.0
    _within_ulps(out, RECORDED[f"b_{dname}_out"], dname, steps)
    _within_ulps(gx, RECORDED[f"b_{dname}_dx"], dname, steps)
    for k in ("fc_kernel", "fc_bias", "fc_out_kernel", "ln2_scale",
              "qkv_kernel"):
        _within_ulps(gp[k], RECORDED[f"b_{dname}_d_{k}"], dname, steps)


def test_not_the_per_operation_bf16_chain():
    """The control for the test above: ``jax.nn.gelu`` on a bf16 tensor
    rounds after every operation and lands several bf16 steps from the
    recorded kernel where ``bias_gelu`` lands within one."""
    y = jnp.asarray(RECORDED["k_y"]).astype(jnp.bfloat16)
    b = jnp.asarray(RECORDED["k_bias"])
    chain = jax.nn.gelu(y + b.astype(y.dtype), approximate=True)
    with pytest.raises(AssertionError):
        _within_ulps(chain, RECORDED["k_bf16_tanh_out"], "bf16", 1.0,
                     floor=2.0 ** -6)


# --------------------------------------------------------------------- #
# (d) serving's mlp is the training block's
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dname", ["f32", "bf16"])
def test_decode_mlp_at_three_rows_is_the_training_blocks(dname):
    from deepspeed_tpu.inference.decode import _ffn
    cfg, params, x = _block(dtype=DTYPES[dname])
    # With the attention projection zeroed the block is x + FFN(LN2(x)).
    params = dict(params, proj_kernel=jnp.zeros_like(params["proj_kernel"]),
                  proj_bias=jnp.zeros_like(params["proj_bias"]),
                  fc_bias=jnp.asarray(np.random.default_rng(2).standard_normal(
                      params["fc_bias"].shape), jnp.float32))
    rows = x[:1, :3]                                       # [1, 3, H]
    # Op by op, so that each side rounds where its code says and nowhere
    # else (a compiled program keeps excess precision where it likes).
    train = transformer_block(params, rows, cfg)
    serve = _ffn(params, rows[0], cfg)
    np.testing.assert_array_equal(np.asarray(train[0].astype(jnp.float32)),
                                  np.asarray(serve.astype(jnp.float32)))

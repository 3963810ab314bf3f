"""Chip-compiler tests: every Pallas kernel of the main path, at its
gpt2-large shape, compiled for a DESCRIBED (not attached) v5e chip.

Interpret mode (how every other tier-1 test runs these kernels) cannot
see what the TPU compiler refuses — block shapes off the (8, 128)
tiling, over-budget VMEM, unsupported in-kernel ops.  These tests hand
each kernel to the real compiler (``jax.jit(...).lower(shapes).compile()``
against a ``v5e:2x2`` topology description) and assert a
``tpu_custom_call`` came out.  A compile that passes is a compile, not a
run: numerics stay with the interpret-mode suites.

All chip-compiler tests live in THIS file: only one process may load
the TPU library, so the topology is described inside a module-scoped
fixture (never at import) and compiled in the test's own process.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

# gpt2-large: hidden 1280, 20 heads x 64, FFN 5120; bench micro-batch 4
# at S=1024 -> 4096 rows.
MBS, S, NH, D = 4, 1024, 20, 64
H, F = 1280, 5120
ROWS = MBS * S


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch, tmp_path, no_persistent_cache):
    """Steer the kernels' own platform switches (``_interpret()``, the
    ``memory_space`` BlockSpec branches, the ``auto`` knobs) onto their
    TPU side: they all ask ``jax.default_backend()``.  The autotune
    registry is pointed at an empty file so the tiles are the ones a
    cold chip run traces with."""
    monkeypatch.setattr(jax, "default_backend", lambda *a, **k: "tpu")
    monkeypatch.setenv("DS_AUTOTUNE_REGISTRY", str(tmp_path / "reg.json"))
    from deepspeed_tpu.ops import autotune
    autotune.reset()
    yield
    autotune.reset()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _grad_of(fn, argnums):
    """Scalarise and differentiate: compiles forward AND backward."""
    def loss(*a):
        out = fn(*a)
        return sum(jnp.sum(o.astype(jnp.float32) ** 2)
                   for o in jax.tree_util.tree_leaves(out))
    return jax.grad(loss, argnums=argnums)


# ------------------------------------------------------------------ #
# Kernel cases: name -> (function, shapes).  Built lazily (inside the
# test) so nothing touches jax at collection time.
# ------------------------------------------------------------------ #
def _case_flash(bwd):
    from deepspeed_tpu.ops.flash_attention import flash_attention
    fn = functools.partial(flash_attention, causal=True)
    if bwd:
        fn = _grad_of(fn, (0, 1, 2))
    x = _sds((MBS, S, NH, D), jnp.bfloat16)
    return fn, (x, x, x)


def _case_ln(bwd):
    from deepspeed_tpu.ops.fused_elementwise import fused_layer_norm
    fn = fused_layer_norm if not bwd else _grad_of(fused_layer_norm,
                                                   (0, 1, 2))
    return fn, (_sds((ROWS, H), jnp.bfloat16), _sds((H,), jnp.float32),
                _sds((H,), jnp.float32))


def _case_resid_ln(bwd):
    from deepspeed_tpu.ops.fused_elementwise import (
        fused_residual_layer_norm)
    fn = fused_residual_layer_norm if not bwd else \
        _grad_of(fused_residual_layer_norm, (0, 1, 2, 3))
    x = _sds((ROWS, H), jnp.bfloat16)
    return fn, (x, x, _sds((H,), jnp.float32), _sds((H,), jnp.float32))


def _case_gelu(bwd):
    from deepspeed_tpu.ops.fused_elementwise import fused_bias_gelu
    fn = fused_bias_gelu if not bwd else _grad_of(fused_bias_gelu, (0, 1))
    return fn, (_sds((ROWS, F), jnp.bfloat16), _sds((F,), jnp.float32))


# Fused update: 400 grid steps' worth of flat buffer at the original
# (128, 1024) block — the partials array the compiler refused was
# (400, 128).
_UPD_N = 400 * 128 * 1024


def _case_sqnorm(_):
    from deepspeed_tpu.ops.fused_update import _run_sqnorm
    return _run_sqnorm, (_sds((_UPD_N,), jnp.float32),)


def _case_apply(_):
    """One-pass apply, bf16 params with in-kernel stochastic rounding —
    the master-free default of the bench configuration."""
    from deepspeed_tpu.ops.fused_update import _run_group
    fn = functools.partial(
        _run_group, b1=0.9, b2=0.999, eps=1e-8, wd=0.01, coupled=False,
        use_inv=False, use_coeff=True, one_pass=True, sr=True, cast=False,
        out_dtype=jnp.dtype(jnp.bfloat16), cast_dtype=None)
    f32 = _sds((_UPD_N,), jnp.float32)
    return fn, (f32, _sds((_UPD_N,), jnp.bfloat16), f32, f32,
                _sds((1, 8), jnp.float32), _sds((1, 2), jnp.int32))


def _case_paged(K):
    """Serving attend: 8 streams, block_size 16, a 1024-token table."""
    from deepspeed_tpu.ops.paged_attention import paged_attention
    G, Q, B, bs, J = 1, 8, 512, 16, 64
    fn = functools.partial(paged_attention, scale=1.0 / math.sqrt(D))
    pool = _sds((G, B, NH, bs, D), jnp.bfloat16)
    return fn, (_sds((G, Q, K, NH, D), jnp.bfloat16), pool, pool,
                _sds((G, Q, J), jnp.int32), _sds((G, Q, K), jnp.int32))


def _case_grouped(bwd):
    from deepspeed_tpu.ops.grouped_gemm import grouped_ffn
    E, C = 8, 512
    fn = grouped_ffn if not bwd else _grad_of(grouped_ffn, (0, 1, 2, 3, 4))
    return fn, (_sds((E, C, H), jnp.bfloat16), _sds((E, H, F), jnp.bfloat16),
                _sds((E, F), jnp.bfloat16), _sds((E, F, H), jnp.bfloat16),
                _sds((E, H), jnp.bfloat16))


def _case_sparse(_):
    """bench_sparse.py's shape: BigBird, 4 heads, S=32768, D=64,
    forward + backward."""
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        BigBirdSparsityConfig)
    from deepspeed_tpu.ops.sparse_flash import sparse_flash_attention
    heads, seq = 4, 32768
    layout = np.asarray(BigBirdSparsityConfig(
        num_heads=heads, block=128,
        different_layout_per_head=False).make_layout(seq))
    fn = _grad_of(functools.partial(
        sparse_flash_attention, layout=layout, causal=True,
        scale=1.0 / math.sqrt(D)), (0, 1, 2))
    x = _sds((heads, seq, D), jnp.bfloat16)
    return fn, (x, x, x)


CASES = {
    "flash_fwd": (_case_flash, False),
    "flash_bwd": (_case_flash, True),
    "fused_ln_fwd": (_case_ln, False),
    "fused_ln_bwd": (_case_ln, True),
    "fused_residual_ln_fwd": (_case_resid_ln, False),
    "fused_residual_ln_bwd": (_case_resid_ln, True),
    "bias_gelu_fwd": (_case_gelu, False),
    "bias_gelu_bwd": (_case_gelu, True),
    "fused_update_sqnorm": (_case_sqnorm, None),
    "fused_update_apply_bf16_sr": (_case_apply, None),
    "paged_attention_decode_k1": (_case_paged, 1),
    "paged_attention_verify_k5": (_case_paged, 5),
    "paged_attention_prefill_k32": (_case_paged, 32),
    "grouped_gemm_ffn_fwd": (_case_grouped, False),
    "grouped_gemm_ffn_bwd": (_case_grouped, True),
    "sparse_flash_fwd_bwd": (_case_sparse, None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, as_tpu):
    build, arg = CASES[name]
    fn, shapes = build(arg)
    _compile(fn, one_chip, *shapes)

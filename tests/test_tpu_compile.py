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
import re

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
def _case_flash(bwd, mbs=MBS, nh=NH, dropout=0.0):
    """Causal flash at a train cell's tile.  With ``dropout`` the call is
    the cells' own (attn_pdrop 0.1, a traced rng): the row-banded bodies
    with the keep-mask hash in them."""
    from deepspeed_tpu.ops.flash_attention import flash_attention

    def fn(q, k, v, rng):
        return flash_attention(q, k, v, causal=True, attn_dropout=dropout,
                               rng=rng, deterministic=not dropout)
    x = _sds((mbs, S, nh, D), jnp.bfloat16)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return (_grad_of(fn, (0, 1, 2)) if bwd else fn), (x, x, x, key)


def _case_flash_qkv(bwd, mbs=4, nh=20):
    """A train cell's call as `transformer_block` makes it: the fused
    [B, S, 3H] projection read in place, attn_pdrop 0.1."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_qkv

    def fn(qkv, rng):
        return flash_attention_qkv(qkv, nh, causal=True, attn_dropout=0.1,
                                   rng=rng, deterministic=False)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return (_grad_of(fn, (0,)) if bwd else fn), (
        _sds((mbs, S, 3 * nh * D), jnp.bfloat16), key)


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


def _case_update_leaf(shape, gdt=jnp.float32):
    """The same kernel over ONE leaf where it lies (the plan's in-place
    leaves): gpt2-large's matrices through their collapsed 2-D view,
    fp32 masters with the bf16 cast output, and a dp=8 shard of wte whose
    rows leave a ragged last block. ``gdt``: the gradient operand's
    width — f32 from every path that sums gradients, bf16 as the
    one-device backward writes it (the train cells' program)."""
    from deepspeed_tpu.ops.fused_update import _update_leaf
    sr = shape != (36, 1280, 1280)
    pdt = jnp.bfloat16 if sr else jnp.float32
    fn = functools.partial(
        _update_leaf, b1=0.9, b2=0.999, eps=1e-8, wd=0.01, coupled=False,
        use_inv=False, use_coeff=True, one_pass=True, sr=sr, cast=not sr,
        out_dtype=jnp.dtype(pdt),
        cast_dtype=None if sr else jnp.dtype(jnp.bfloat16))
    f32 = _sds(shape, jnp.float32)
    return fn, (_sds(shape, gdt), _sds(shape, pdt), f32, f32,
                _sds((1, 8), jnp.float32), _sds((1, 2), jnp.int32))


def _case_paged(K):
    """Serving attend: 8 streams, block_size 16, a 1024-token table, one
    layer of a stacked lane-dense pool picked by the layer operand."""
    from deepspeed_tpu.inference.kv_cache import PagedKVCacheSpec
    from deepspeed_tpu.ops.paged_attention import paged_attention
    G, Q, B, bs, J = 1, 8, 512, 16, 64
    fn = functools.partial(paged_attention, scale=1.0 / math.sqrt(D))
    pool = _sds(PagedKVCacheSpec(
        num_layers=2, num_slots=Q, num_blocks=B, block_size=bs,
        max_len=J * bs, num_heads=NH, head_dim=D).shape, jnp.bfloat16)
    return fn, (_sds((G, Q, K, NH, D), jnp.bfloat16), pool, pool,
                _sds((), jnp.int32), _sds((G, Q, J), jnp.int32),
                _sds((G, Q, K), jnp.int32))


def _case_grouped(bwd):
    from deepspeed_tpu.ops.grouped_gemm import grouped_ffn
    E, C = 8, 512
    fn = grouped_ffn if not bwd else _grad_of(grouped_ffn, (0, 1, 2, 3, 4))
    return fn, (_sds((E, C, H), jnp.bfloat16), _sds((E, H, F), jnp.bfloat16),
                _sds((E, F), jnp.bfloat16), _sds((E, F, H), jnp.bfloat16),
                _sds((E, H), jnp.bfloat16))


def _case_sparse(_):
    """The long-context sparse shape: BigBird, 4 heads, S=32768, D=64,
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


# What the two train cells run in every layer: micro-batch x heads of
# 1024 x 64, attn_pdrop 0.1.
_FLASH_LARGE = functools.partial(_case_flash, mbs=4, nh=20, dropout=0.1)
_FLASH_MEDIUM = functools.partial(_case_flash, mbs=8, nh=16, dropout=0.1)

CASES = {
    "flash_fwd": (_case_flash, False),
    "flash_bwd": (_case_flash, True),
    "flash_fwd_dropout_gpt2_large": (_FLASH_LARGE, False),
    "flash_bwd_dropout_gpt2_large": (_FLASH_LARGE, True),
    "flash_fwd_dropout_gpt2_medium": (_FLASH_MEDIUM, False),
    "flash_bwd_dropout_gpt2_medium": (_FLASH_MEDIUM, True),
    "flash_qkv_fwd_gpt2_large": (_case_flash_qkv, False),
    "flash_qkv_bwd_gpt2_large": (_case_flash_qkv, True),
    "flash_qkv_bwd_gpt2_medium": (
        functools.partial(_case_flash_qkv, mbs=8, nh=16), True),
    "fused_ln_fwd": (_case_ln, False),
    "fused_ln_bwd": (_case_ln, True),
    "fused_residual_ln_fwd": (_case_resid_ln, False),
    "fused_residual_ln_bwd": (_case_resid_ln, True),
    "fused_update_sqnorm": (_case_sqnorm, None),
    "fused_update_apply_bf16_sr": (_case_apply, None),
    "fused_update_leaf_wte": (_case_update_leaf, (50304, 1280)),
    "fused_update_leaf_wte_dp8_shard": (_case_update_leaf, (6288, 1280)),
    "fused_update_leaf_wpe": (_case_update_leaf, (1024, 1280)),
    "fused_update_leaf_qkv": (_case_update_leaf, (36, 1280, 3840)),
    "fused_update_leaf_proj_f32_cast": (_case_update_leaf, (36, 1280, 1280)),
    "fused_update_leaf_fc": (_case_update_leaf, (36, 1280, 5120)),
    "fused_update_leaf_fc2": (_case_update_leaf, (36, 5120, 1280)),
    "fused_update_leaf_medium_qkv": (_case_update_leaf, (24, 1024, 3072)),
    "fused_update_leaf_medium_fc2": (_case_update_leaf, (24, 4096, 1024)),
    "paged_attention_decode_k1": (_case_paged, 1),
    "paged_attention_verify_k5": (_case_paged, 5),
    "paged_attention_prefill_k32": (_case_paged, 32),
    "grouped_gemm_ffn_fwd": (_case_grouped, False),
    "grouped_gemm_ffn_bwd": (_case_grouped, True),
    "sparse_flash_fwd_bwd": (_case_sparse, None),
}


# The train cells' program: every whole leaf again with the gradient
# operand as the one-device backward writes it (a dp shard's is f32).
CASES.update({
    name + "_bf16_grad": (functools.partial(build, gdt=jnp.bfloat16), arg)
    for name, (build, arg) in list(CASES.items())
    if build is _case_update_leaf and "shard" not in name})


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, as_tpu):
    build, arg = CASES[name]
    fn, shapes = build(arg)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("mbs, nh", [(4, 20), (8, 16)],
                         ids=["gpt2_large", "gpt2_medium"])
def test_flash_reads_the_fused_projection_in_place(mbs, nh, one_chip, as_tpu):
    """Forward and backward of a train cell's attention call over the
    fused [B, S, 3H] projection, compiled: the two kernels, and no copy or
    transpose of anything as large as q (the relayout path's program holds
    a dozen); what is left of that size beside them is the concatenate of
    dq / dk / dv and the test's own loss."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    from deepspeed_tpu.ops import flash_attention as fa
    before = dict(fa.lowered)
    fn, shapes = _case_flash_qkv(True, mbs=mbs, nh=nh)
    text = _compile(fn, one_chip, *shapes)
    assert fa.lowered["in_place"] == before["in_place"] + 1
    assert fa.lowered["relayout"] == before["relayout"]
    large = {op for op, _ in ops_in_units_of(text, mbs * S * nh * D)}
    assert not large & {"copy", "transpose", "slice"}, large
    for kernel in ("_fwd_kernel", "_bwd_fused_kernel"):
        assert sum(1 for line in text.splitlines()
                   if kernel in line.split(" = ")[0]
                   and "tpu_custom_call" in line) == 1, kernel


def _tanh_carriers(hlo_text):
    """{instruction: holds a GEMM} for every instruction outside a fused
    computation whose callees, nested fusions included, evaluate a tanh."""
    import re
    from deepspeed_tpu.analysis.hlo_text import split_computations
    comps = split_computations(hlo_text)
    calls = re.compile(r"calls=%?([\w.\-]+)")
    fused = {c for lines in comps.values() for l in lines if " fusion(" in l
             for c in calls.findall(l)}

    def body(c, seen):
        if c in seen or c not in comps:
            return ""
        seen.add(c)
        return "\n".join(comps[c]) + "".join(
            body(d, seen) for l in comps[c] for d in calls.findall(l))

    out = {}
    for c, lines in comps.items():
        if c in fused:
            continue
        for l in lines:
            if " fusion(" in l:
                text = body(calls.search(l).group(1), set())
                if " tanh(" in text:
                    out[l.split(" = ")[0].strip()] = " convolution(" in text
    return out


@pytest.mark.parametrize("rows, h, f", [(4096, 1280, 5120),
                                        (8192, 1024, 4096)],
                         ids=["gpt2_large", "gpt2_medium"])
def test_bias_gelu_rides_the_ffn_gemms(rows, h, f, one_chip,
                                       no_persistent_cache):
    """Forward and backward of the FFN sublayer as the train cells run it
    (layers under ``scan``, ``checkpoint_dots``, bf16 compute over fp32
    params), compiled: no kernel of ours, and every op that evaluates the
    GELU's tanh is a GEMM — the forward's in the down-projection's
    operand, the backward's in the output of the GEMM that makes ``da``
    and in the operand of ``dW_out``'s.  A tanh in an op without
    a GEMM is a pass of its own over ``[rows, F]``, which is what the
    deleted Pallas kernels were (PR 51)."""
    from deepspeed_tpu.models.transformer import (TransformerConfig, dense,
                                                  gelu_dense_fn)
    cfg = TransformerConfig(hidden_size=h, intermediate_size=f)
    up = gelu_dense_fn(cfg)

    def layer(x, p):
        a = up(x, p["fc_kernel"], p["fc_bias"])
        return x + dense(a, p["fc_out_kernel"], p["fc_out_bias"]), None

    def loss(params, x):
        x, _ = jax.lax.scan(jax.checkpoint(
            layer, policy=jax.checkpoint_policies.checkpoint_dots), x, params)
        return jnp.sum(x.astype(jnp.float32) ** 2)

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layers = 4
    params = {"fc_kernel": sds(layers, h, f), "fc_bias": sds(layers, f),
              "fc_out_kernel": sds(layers, f, h),
              "fc_out_bias": sds(layers, h)}
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, sds(rows, h, dtype=jnp.bfloat16)).compile().as_text()
    assert "tpu_custom_call" not in text
    carriers = _tanh_carriers(text)
    # One in the forward and two in the backward: the recomputed GELU in
    # dW_out's operand, and the GEMM that makes ``da`` writing ``dy`` and
    # ``dbias``.  Five is ``dy`` evaluated again in the operands of its
    # two readers (what the barrier in ``_bias_gelu_bwd`` is there for).
    assert 1 <= len(carriers) <= 3, carriers
    assert all(carriers.values()), \
        f"a tanh outside a GEMM: {[k for k, v in carriers.items() if not v]}"


@pytest.mark.parametrize("K,Q", [(1, 64), (128, 1)],
                         ids=["decode_k1_64_streams", "prefill_k128"])
def test_paged_attention_at_the_serve_cells_shape(K, Q, one_chip, as_tpu):
    """The attend ALONE at `serve.gpt2-large.chat-over`'s shapes (64
    slots or one 128-row chunk, 20 heads x 64, 1280 blocks of 16, table
    64, 36 layers, bf16) under the shape rule's own tiles: nothing in
    the program but parameters, bitcasts and the kernel holds the pool
    or a layer of it, its temporaries are a few MiB at most, and the
    kernel fits the scoped VMEM it asks for (the compiler refuses one
    that does not) — the default 16 MiB, with the rule's own reckoning
    of a step inside its budget."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    from deepspeed_tpu.inference.kv_cache import PagedKVCacheSpec
    from deepspeed_tpu.ops import paged_attention as pa
    spec = PagedKVCacheSpec(
        num_layers=36, num_slots=SERVE["max_slots"],
        num_blocks=SERVE["num_blocks"], block_size=SERVE["block_size"],
        max_len=SERVE["max_len"], num_heads=NH, head_dim=D,
        dtype=jnp.bfloat16)
    J = spec.max_blocks_per_slot
    pool = jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=one_chip)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((1, Q, K, NH, D), jnp.bfloat16),
                                 ((), jnp.int32), ((1, Q, J), jnp.int32),
                                 ((1, Q, K), jnp.int32))]
    compiled = jax.jit(functools.partial(
        pa.paged_attention, scale=1.0 / math.sqrt(D))).lower(
        args[0], pool, pool, *args[1:]).compile()
    text = compiled.as_text()
    seen = ops_in_units_of(text, math.prod(spec.shape[2:]))
    assert {op for op, _ in seen} <= {"parameter", "bitcast",
                                      "custom-call"}, seen
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20
    calls = [line for line in text.splitlines()
             if "%_pattn_kernel" in line.split(" = ")[0]
             and " custom-call(" in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert f'"size":"{pa._VMEM_LIMIT}"' in calls[0]
    assert pa._VMEM_LIMIT <= 16 * 2 ** 20
    bh, P = pa._tile_rule(K, NH, D, spec.block_size, J, 2, 2)
    assert pa._step_vmem_bytes(bh, P, K, D, spec.block_size, 2, 2) \
        <= pa._VMEM_BUDGET < pa._VMEM_LIMIT


# The attend's body in the engine's programs of a family whose K/V heads are
# 128 wide (no fold) and whose chunk's runs hold ``_DENSE_ROWS`` query rows a
# K/V head or more: the chunk-shaped one in ``prefill_step`` alone (PR 65).
_ATTEND_KERNEL = {"decode_step": "_pattn_kernel",
                  "verify_step": "_pattn_kernel",
                  "prefill_step": "_pattn_chunk_kernel"}


def _attend_kernel_and_its_vmem(K, tiles, D, bs):
    """The name of the body a step of K query rows a K/V head takes
    (``pa._dense``), its own VMEM reckoning held to its own budget."""
    from deepspeed_tpu.ops import paged_attention as pa
    if pa._dense(K, D, bs):
        assert pa._chunk_vmem_bytes(*tiles, K, D, bs, 2, 2) \
            <= pa._CHUNK_VMEM_BUDGET < pa._VMEM_LIMIT <= 16 * 2 ** 20
        return "_pattn_chunk_kernel"
    assert pa._step_vmem_bytes(*tiles, K, D, bs, 2, 2) \
        <= pa._VMEM_BUDGET < pa._VMEM_LIMIT <= 16 * 2 ** 20
    return "_pattn_kernel"


@pytest.mark.parametrize("what,Q,K,nKV,grp,bs,blocks,J,reach", [
    # `serve.solar-open2-250b.agent-sessions-over`: a chunk of 512 rows in
    # eight runs of 64 rows x 8 query heads a K/V head, blocks of 128
    # behind a table of 1,664 — and its 256-row width's four runs
    ("cell14_chunk_512", 8, 64, 8, 8, 128, 12288, 1664, None),
    ("cell14_chunk_256", 4, 64, 8, 8, 128, 12288, 1664, None),
    # `serve.falcon-h1-34b.chat-short-over`: five query heads a K/V head
    ("cell9_chunk_512", 8, 64, 4, 5, 64, 2048, 48, None),
    # one query head a K/V head and rows alone; a short window's ring
    ("rows_alone", 2, 256, 8, 1, 64, 1024, 64, None),
    ("short_ring", 8, 64, 4, 8, 64, 1024, 5, 128),
])
def test_the_chunk_body_at_the_cells_shapes(what, Q, K, nKV, grp, bs, blocks,
                                            J, reach, one_chip, as_tpu):
    """A prefill run's attend ALONE where its rows a K/V head are
    ``_DENSE_ROWS`` or more (head_dim 128, bf16): ``_pattn_chunk_kernel``
    under the shape rule's own tiles, scoped VMEM asked and kept at the
    default 16 MiB, nothing but parameters, bitcasts and the kernel
    holding a pool or a layer of it.  (Cell 11's runs and cell 6's: the
    two tests below; cell 13's as cell 6's full class.)"""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    from deepspeed_tpu.ops import paged_attention as pa
    Dh = 128
    tiles = pa._tile_rule(grp * K, nKV, Dh, bs, J, 2, 2)
    assert tiles[1] == min(J, pa._CHUNK_KEYS // bs) and nKV % tiles[0] == 0
    assert _attend_kernel_and_its_vmem(grp * K, tiles, Dh, bs) \
        == "_pattn_chunk_kernel"
    shape = (1, 1, blocks, nKV, bs, Dh)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in (((1, Q, K, nKV * grp, Dh), jnp.bfloat16),
                           ((), jnp.int32), ((1, Q, J), jnp.int32),
                           ((1, Q, K), jnp.int32))]

    def attend(q, pk, pv, layer, bt, pos):
        plan = pa.attend_plan(bt, pos, pk, Dh, reach=reach, group=grp)
        return pa.paged_attention(q, pk, pv, layer, plan=plan,
                                  scale=Dh ** -0.5)
    compiled = jax.jit(attend).lower(args[0], pool, pool,
                                     *args[1:]).compile()
    text = compiled.as_text()
    seen = ops_in_units_of(text, math.prod(shape[2:]))
    assert {op for op, _ in seen} <= {"parameter", "bitcast",
                                      "custom-call"}, seen
    calls = [line for line in text.splitlines()
             if "%_pattn_chunk_kernel" in line.split(" = ")[0]
             and " custom-call(" in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert f'"size":"{pa._VMEM_LIMIT}"' in calls[0]
    assert "%_pattn_kernel" not in text


@pytest.mark.parametrize("cls,blocks,layers,J,reach", [
    ("full", 16384, 1, 528, None), ("window", 6144, 4, 41, 2048)])
@pytest.mark.parametrize("K,Q,tiles", [(1, 128, (4, 16)), (64, 8, (4, 8))],
                         ids=["decode_128_streams", "prefill_run_512_rows"])
def test_paged_attention_at_the_mixed_cells_shapes(K, Q, tiles, cls, blocks,
                                                   layers, J, reach,
                                                   one_chip, as_tpu):
    """The attend ALONE at `serve.trinity-mini.mixed-docqa-over`'s shapes
    (4 K/V heads of 128 under 32 query heads, blocks of 64, bf16; the full
    class's 16,384 blocks x 1 layer behind a table of 528 and the window
    class's 6,144 x 4 behind a ring of 41): decode's 8 query rows a K/V
    head walk SIXTEEN slots a group (2 MiB of K and V tiles in flight), a
    prefill run's 512 rows take the chunk body (``_pattn_chunk_kernel``,
    PR 65) at eight slots = 512 keys a group; scoped VMEM asked and kept at
    the default 16 MiB, and nothing but parameters, bitcasts and the kernel
    holds a pool or a layer of it."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    from deepspeed_tpu.ops import paged_attention as pa
    nKV, grp, Dh, bs = 4, 8, 128, 64
    assert pa._tile_rule(grp * K, nKV, Dh, bs, J, 2, 2) == tiles
    kernel = _attend_kernel_and_its_vmem(grp * K, tiles, Dh, bs)
    shape = (layers, 1, blocks, nKV, bs, Dh)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in (((1, Q, K, nKV * grp, Dh), jnp.bfloat16),
                           ((), jnp.int32), ((1, Q, J), jnp.int32),
                           ((1, Q, K), jnp.int32))]

    def attend(q, pk, pv, layer, bt, pos):
        plan = pa.attend_plan(bt, pos, pk, Dh, reach=reach, group=grp)
        return pa.paged_attention(q, pk, pv, layer, plan=plan,
                                  scale=Dh ** -0.5)
    compiled = jax.jit(attend).lower(args[0], pool, pool,
                                     *args[1:]).compile()
    text = compiled.as_text()
    seen = ops_in_units_of(text, math.prod(shape[2:]))
    assert {op for op, _ in seen} <= {"parameter", "bitcast",
                                      "custom-call"}, seen
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20
    calls = [line for line in text.splitlines()
             if f"%{kernel}" in line.split(" = ")[0]
             and " custom-call(" in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert f'"size":"{pa._VMEM_LIMIT}"' in calls[0]


# ------------------------------------------------------------------ #
# The fused optimizer's whole one-pass step at gpt2-large's shapes: what
# the chip compiler makes of the in-place plan (ops/fused_update.py).
# ------------------------------------------------------------------ #
_BIG = 1 << 20          # elements: every in-place gpt2 leaf is larger


@pytest.fixture(scope="module", params=[jnp.float32, jnp.bfloat16],
                ids=["f32_grads", "bf16_grads"])
def optimizer_step(request, topo):
    """(plan summary, lowered text, compiled) of fused_step over the
    scanned gpt2-large tree: bf16 params with stochastic rounding, clip
    1.0, params and state donated; the gradients f32 (what dp > 1 and
    the accumulation scan hand it) and bf16 (cell 1's optimizer: the
    one-device backward's own width — a widening pass ahead of the
    kernels would show as 3 GB of scratch)."""
    from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init
    from deepspeed_tpu.ops import autotune, fused_update
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.eval_shape(
        lambda k: gpt2_init(k, GPT2_CONFIGS["gpt2-large"]),
        jax.random.PRNGKey(0))

    def tree(dtype):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dtype, sharding=one),
            shapes)

    tx = fused_update.fused_adam(lambda c: jnp.float32(1e-4),
                                 weight_decay=0.01)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(tx.init, tree(jnp.float32)))

    def step(g, s, p, key):
        with jax.named_scope("optimizer"):
            out = tx.fused_step(g, s, p, clip=1.0, sr_key=key)
        return out.params, out.state, out.grad_norm

    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        autotune.reset()
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        lowered = jax.jit(step, donate_argnums=(1, 2)).lower(
            tree(request.param), state, tree(jnp.bfloat16),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one))
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
        autotune.reset()
    return (fused_update.plan_summary(tree(jnp.bfloat16)),
            lowered.as_text(), compiled)


def test_optimizer_step_lowers_one_kernel_program_per_geometry(
        optimizer_step):
    """Start-up budget: the lowered step holds at most (distinct large
    leaf geometries + 1) Adam kernel programs and ONE norm kernel (the
    packed group's; in-place leaves reduce in plain XLA)."""
    plan, lowered, _ = optimizer_step
    assert plan["leaves_in_place"] == 6 and plan["kernel_programs"] == 7
    assert lowered.count("_fused_adam_kernel") <= plan["kernel_programs"]
    assert lowered.count("_sqnorm_kernel") == 1


def test_optimizer_step_assembles_no_large_leaf(optimizer_step):
    """The compiled step holds no ``concatenate``, ``copy`` or
    ``transpose`` of a buffer the size of a large leaf: what is still
    assembled is the packed group (0.66M elements at gpt2-large)."""
    _, _, compiled = optimizer_step
    import re
    seen = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* "
                      r"(copy|concatenate|transpose)\(", line)
        if not m:
            continue
        n = int(np.prod([int(d) for d in m.group(1).split(",") if d]))
        seen.append((m.group(2), n))
    assert any(op == "concatenate" for op, _ in seen), \
        "the packed group's assembly should still be there"
    assert all(n < _BIG for _, n in seen), \
        [(op, n) for op, n in seen if n >= _BIG]


def test_optimizer_step_updates_donated_buffers_in_place(optimizer_step):
    """Parameters and both moments of the in-place leaves are donated and
    aliased to the outputs (no second copy: this is what keeps the step's
    peak HBM where it was), and the step needs next to no scratch."""
    plan, _, compiled = optimizer_step
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= plan["bytes_in_place"]
    # parent: 6.2 GB of flat gradient and parameter buffers lived here
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem.temp_size_in_bytes



# ------------------------------------------------------------------ #
# The serving programs at the serve cell's shape (gpt2-large, 64 slots,
# 1280 blocks of 16, chunk 128, pools donated): what the chip compiler
# makes of the pool's passage through decode_step and prefill_step.
# ------------------------------------------------------------------ #
SERVE = dict(max_slots=64, block_size=16, num_blocks=1280, max_len=1024,
             prefill_chunk=128)
_POOL_OPS_ALLOWED = {"parameter", "tuple", "get-tuple-element", "while",
                     "bitcast", "custom-call"}


def _serve_program(topo, program, head_dim):
    """(spec, params bytes, compiled) of the ENGINE's own step builder
    (``InferenceEngine._build_decode_step`` / ``_build_prefill_step``) on
    an engine shell that holds just what the builders read: building a
    real engine puts arrays on a device, which a described chip cannot
    hold."""
    import dataclasses
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init
    from deepspeed_tpu.ops import autotune
    from jax.experimental.compilation_cache import compilation_cache
    one = SingleDeviceSharding(topo.devices[0])
    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-large"],
                              dtype=jnp.bfloat16)
    cfg = dataclasses.replace(cfg, num_heads=cfg.hidden_size // head_dim)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    params = on_chip(jax.eval_shape(
        lambda k: jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype),
                                         gpt2_init(k, cfg)),
        jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    spec = kv_cache.PagedKVCacheSpec(
        num_layers=cfg.num_layers, num_slots=SERVE["max_slots"],
        num_blocks=SERVE["num_blocks"], block_size=SERVE["block_size"],
        max_len=SERVE["max_len"], num_heads=cfg.num_heads,
        head_dim=cfg.head_dim, dtype=jnp.bfloat16)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = cfg, 1, 1, None
    eng.paged_kernel = True
    eng.quantize = "none"
    eng.prefill_chunk = SERVE["prefill_chunk"]
    eng._cache_sh = {"k": one, "v": one}
    S, J, C = spec.num_slots, spec.max_blocks_per_slot, eng.prefill_chunk
    pool = on_chip(jax.ShapeDtypeStruct(spec.shape, spec.dtype))
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        autotune.reset()
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        if program == "decode_step":
            step = eng._build_decode_step()
            args = (params, pool, pool, i32(S), i32(S), fresh(S), i32(S),
                    i32(S, J), key, temp)
        elif program == "copy_block":
            step = eng._build_copy("copy_block", "cow_copy")
            args = (pool, pool, i32(1), i32(1))
        else:
            step = eng._build_prefill_step()
            args = (params, pool, pool, i32(1, C), i32(1, J), i32(1),
                    i32(1), i32(1), i32(), key, temp)
        compiled = step.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
        autotune.reset()
    return spec, param_bytes, compiled


@pytest.fixture(scope="module")
def serve_programs(topo):
    cache = {}

    def get(program, head_dim):
        if (program, head_dim) not in cache:
            cache[program, head_dim] = _serve_program(topo, program,
                                                      head_dim)
        return cache[program, head_dim]
    return get


# head_dim 64 folds two positions into the lanes; 128 does not fold: the
# adapting branch is compiled too.
SERVE_CASES = [("decode_step", 64), ("prefill_step", 64),
               ("decode_step", 128)]
_serve_cases = pytest.mark.parametrize("program,head_dim", SERVE_CASES)


@_serve_cases
def test_serve_step_holds_no_pool_sized_operation(serve_programs, program,
                                                  head_dim):
    """No instruction but parameters, tuples, the layer ``while``,
    bitcasts and the (aliased) kernels has an output the size of one
    layer's pool or of any whole number of layers: no ``copy``,
    ``select``, ``convolution``, ``dynamic-slice``,
    ``dynamic-update-slice``, ``scatter`` or fusion of them. (The parent
    held a slice + two relayout copies + an update per layer, a one-hot
    convolution + two selects per layer, and two whole-pool copies.)"""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    spec, _, compiled = serve_programs(program, head_dim)
    seen = ops_in_units_of(compiled.as_text(), math.prod(spec.shape[2:]))
    found = [(op, n) for op, n in seen if op not in _POOL_OPS_ALLOWED]
    assert not found, found
    # the reader does see the pool where it legitimately is
    assert any(op == "custom-call" for op, _ in seen)
    assert any(op == "while" for op, _ in seen)


@_serve_cases
def test_serve_step_updates_the_donated_pools_in_place(serve_programs,
                                                       program, head_dim):
    """Both pools are aliased to the outputs and the step needs next to
    no scratch (parent: 4.30 GiB of temporaries beside 3.52 aliased)."""
    spec, _, compiled = serve_programs(program, head_dim)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= spec.nbytes()
    assert mem.temp_size_in_bytes < 256 * 2 ** 20, mem.temp_size_in_bytes


def test_the_block_copy_updates_one_block_in_place(serve_programs):
    """A copy-on-write fork at the serve cell's shape is the page copy
    every pool has: both pools aliased, a block's rows of scratch, ONE
    in-place ``dynamic-update-slice`` a pool and no select or contraction
    over the pool (what the one-hot copy was)."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    spec, _, compiled = serve_programs("copy_block", 64)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= spec.nbytes()
    assert mem.temp_size_in_bytes <= 4 * spec.block_nbytes(), \
        mem.temp_size_in_bytes
    seen = ops_in_units_of(compiled.as_text(), math.prod(spec.shape[2:]))
    found = [(op, n) for op, n in seen if op not in _POOL_OPS_ALLOWED
             | {"dynamic-update-slice", "fusion"}]
    assert not found, found
    assert any(op == "dynamic-update-slice" for op, _ in seen)


@_serve_cases
def test_serve_step_holds_the_pools_unpadded_in_the_default_layout(
        serve_programs, one_chip, program, head_dim):
    """No ``Format`` is pinned: the pools enter and leave in the layout
    the compiler gives their shape by default (what a one-line update of
    such an array compiles with), which is row-major, and unpadded: the
    program's arguments are the weights and ``spec.nbytes()`` of pool."""
    spec, param_bytes, compiled = serve_programs(program, head_dim)
    pool = jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=one_chip)
    default = jax.jit(lambda x: x.at[0, 0, 0].add(1)).lower(
        pool).compile().input_formats[0][0].layout
    assert default.major_to_minor == tuple(range(len(spec.shape)))
    formats = compiled.input_formats[0]
    assert formats[1].layout == default and formats[2].layout == default
    out = compiled.output_formats
    assert out[0].layout == default and out[1].layout == default
    args = compiled.memory_analysis().argument_size_in_bytes
    assert args - param_bytes - spec.nbytes() < 16 * 2 ** 20, \
        (args, param_bytes, spec.nbytes())


@_serve_cases
def test_serve_step_kernels_lower_to_tpu_custom_calls(serve_programs,
                                                      program, head_dim):
    _, _, compiled = serve_programs(program, head_dim)
    text = compiled.as_text()
    for kernel in ("_pattn_kernel", "_kv_write_kernel"):
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel


# ------------------------------------------------------------------ #
# The latent-attention configuration (PR 32): its three kernels at the
# published widths, and the engine's decode / prefill programs at the
# benchmark cell's shape (perfbench/configs/gigachat3.1-702b-a36b.json:
# 128 slots, blocks of 64, 7,168 blocks, chunk 512, five layers, 16 of
# 256 experts held).
# ------------------------------------------------------------------ #
LATENT = dict(L=5, B=7168, h=32, W=1152, J=272, nH=64, C=512, R=64)
# ... and the other published shape that runs them (PR 41,
# perfbench/configs/xing4.0-29b-a4b.json: 256 slots of 32 heads, a table
# of 96, 12,288 blocks over six layers).
LATENT_32_HEADS = dict(LATENT, L=6, B=12288, J=96, nH=32)


def _latent_pool(c=LATENT):
    return _sds((c["L"], 1, c["B"], 1, c["h"], c["W"]), jnp.bfloat16)


@pytest.mark.parametrize("c,Q,K", [
    (LATENT, 128, 1), (LATENT, 1, 512), (LATENT, 128, 3),
    (LATENT_32_HEADS, 256, 1), (LATENT_32_HEADS, 1, 512)],
    ids=["decode", "prefill-chunk", "verify", "decode-32-heads-table-96",
         "prefill-chunk-32-heads"])
def test_latent_attention_compiles_at_the_published_widths(c, Q, K,
                                                           one_chip, as_tpu):
    """Both published decode shapes (groups of 32 slots computed at 32 /
    16 / 8 / 4: 4.7 MB of buffer), the prefill row tile and verify, inside
    the VMEM the call asks for (the compiler refuses what does not
    fit)."""
    from deepspeed_tpu.ops import latent_attention as la

    def fn(qa, qr, pool, layer, bt, pos):
        plan = la.latent_plan(bt, pos, pool)
        return la.latent_attention(qa, qr, pool, layer, plan=plan,
                                   scale=0.14)
    _compile(fn, one_chip,
             _sds((1, Q, K, c["nH"], c["C"]), jnp.bfloat16),
             _sds((1, Q, K, c["nH"], c["R"]), jnp.bfloat16), _latent_pool(c),
             _sds((), jnp.int32), _sds((1, Q, c["J"]), jnp.int32),
             _sds((1, Q, K), jnp.int32))


@pytest.mark.parametrize("rows", [128, 512])
def test_latent_write_compiles_at_the_published_widths(rows, one_chip,
                                                       as_tpu):
    from deepspeed_tpu.ops import latent_attention as la
    c = LATENT
    _compile(lambda pool, new, layer, blk, off: la.latent_write(
        pool, new, layer, blk, off, kv_lora=c["C"]), one_chip,
        _latent_pool(), _sds((1, rows, c["C"] + c["R"]), jnp.bfloat16),
        _sds((), jnp.int32), _sds((1, rows), jnp.int32),
        _sds((1, rows), jnp.int32))


@pytest.mark.parametrize("what,nH,bs,D,streams,K,one_block", [
    # cell 13's block pass: 256 slots x a block of 4 rows in a page of 64
    ("a_block_a_slot", 4, 64, 128, 256, 4, True),
    # cell 11's 512-row chunk (both classes' pages are [4, 64, 128]) ...
    ("a_chunk", 4, 64, 128, 1, 512, False),
    # ... cell 8's at head_dim 64, two positions a lane row (eight heads: in
    # two parts) ...
    ("a_folded_chunk", 8, 64, 64, 1, 512, False),
    # ... cell 14's pages of 128 rows under eight heads (written in parts)
    ("a_chunk_in_parts", 8, 128, 128, 1, 512, False),
    # and a row a stream: cell 2's 64 slots (folded), cell 13's 256
    ("a_row_of_64_slots", 20, 16, 64, 64, 1, False),
    ("a_row_of_256_slots", 4, 64, 128, 256, 1, False)])
def test_kv_write_compiles_at_the_cells_shapes(what, nH, bs, D, streams, K,
                                               one_block, one_chip, as_tpu):
    """The K/V write at the published shapes it meets, a RUN of a stream's
    rows a grid step where a stream brings more than one: ONE kernel
    instance a call, and the grid ``write_step_counts`` says."""
    from deepspeed_tpu.ops import paged_attention as pa
    f = pa._fold(D, bs)
    pool = _sds((6, 1, 600, nH, bs // f, f * D), jnp.bfloat16)
    new = _sds((1, streams * K, nH, D), jnp.bfloat16)
    at = _sds((1, streams * K), jnp.int32)
    text = _compile(
        lambda pk, pv, k, v, layer, blk, off: pa.paged_write(
            pk, pv, k, v, layer, blk, off, stream_rows=K,
            one_block=one_block),
        one_chip, pool, pool, new, new, _sds((), jnp.int32), at, at)
    assert text.count("tpu_custom_call") == 1
    steps = pa.write_step_counts(np.zeros(streams), np.full(streams, K), K=K,
                                 block_size=bs, one_block=one_block,
                                 num_heads=nH, head_dim=D)[2]
    assert steps == {"a_block_a_slot": 256, "a_chunk": 9, "a_folded_chunk": 10,
                     "a_chunk_in_parts": 6, "a_row_of_64_slots": 64,
                     "a_row_of_256_slots": 256}[what]


def _compile_grouped(one_chip, T, H, M, tm, w, act="silu"):
    """``grouped_swiglu`` alone over ``T`` tokens of width ``H`` and a plan
    of ``M`` buffer rows in tiles of ``tm``, weights ``w`` (a shape)."""
    from deepspeed_tpu.ops import grouped_gemm as gg
    tiles = _sds((M // tm,), jnp.int32)
    return _compile(lambda x, src, wg, wu, wd, te, tr, nl: gg.grouped_swiglu(
        x, src, wg, wu, wd, te, tr, nl, tm=tm, act=act), one_chip,
        _sds((T, H), jnp.bfloat16), _sds((M,), jnp.int32), w, w, w, tiles,
        tiles, _sds((), jnp.int32))


@pytest.mark.parametrize("tokens", [128, 512], ids=["decode", "prefill"])
def test_grouped_swiglu_compiles_at_the_published_widths(tokens, one_chip,
                                                         as_tpu):
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config
    from deepspeed_tpu.moe import share
    cfg = DeepseekV3Config(held=(0, 16))
    tm = share._row_tile(tokens, cfg.routing)
    assert tm == (16 if tokens == 128 else 32)
    M = -(-tokens * 8 // tm) * tm + 16 * tm         # the worst routing
    w = _sds((4 * 16, 2048, 7168), jnp.bfloat16)    # four layers' experts
    _compile_grouped(one_chip, tokens, 7168, M, tm, w)


# (decode rows, H, F, routed experts, experts held, per token): the six
# expert-layer cells of BENCHMARK.json, perfbench/configs/<name>.json.
EXPERT_CELLS = {
    "gigachat": (128, 7168, 2048, 256, 16, 8),
    "trinity": (128, 2048, 1024, 128, 128, 8),
    "xing": (256, 3584, 1024, 64, 64, 4),
    "lfm2": (128, 2048, 1536, 64, 64, 4),
    "kimi": (256, 2304, 1024, 256, 16, 8),
    "smallthinker": (64, 2560, 768, 64, 64, 6),
}


@pytest.mark.parametrize("width", ["decode", "chunk"])
@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_the_grouped_product_reads_its_rows_at_every_cells_widths(
        cell, width, one_chip, as_tpu):
    """The product holds the tokens ``[T, H]`` whole in VMEM as float32 and
    the plan's ``src`` (the worst routing's ``M`` int32: 12,288 in the
    trinity cell's chunk) in SMEM beside the weights' tiles: every cell's
    decode iteration and 512-row chunk program must fit the chip's."""
    from deepspeed_tpu.models.blocks import Routing
    from deepspeed_tpu.moe import share
    slots, H, F, experts, held, k = EXPERT_CELLS[cell]
    T = slots if width == "decode" else 512
    tm = share._row_tile(T, Routing(experts, k, 1, 1, True, 1.0, (0, held)))
    M = -(-T * k // tm) * tm + held * tm             # the worst routing
    w = _sds((2 * held, F, H), jnp.bfloat16)         # two layers' experts
    text = _compile_grouped(one_chip, T, H, M, tm, w)
    assert "_gswiglu_kernel" in text


def test_the_grouped_product_narrows_its_f_tile_for_a_long_chunk(one_chip,
                                                                 as_tpu):
    """A chunk of 1,024 rows at the widest cell's H: the tokens take 44 MB
    of VMEM (as they are and as float32) and the weights' tiles what is
    left (an F tile of 256 where 512 rows' chunk has 512)."""
    T, H, F, held, k, tm = 1024, 7168, 2048, 16, 8, 128
    M = T * k + held * tm
    w = _sds((held, F, H), jnp.bfloat16)
    text = _compile_grouped(one_chip, T, H, M, tm, w)
    assert "_gswiglu_kernel" in text


def _latent_cell_programs(topo, config_file, **overrides):
    """(spec, params bytes, {program: compiled}) of the ENGINE's own step
    builders for a latent-attention cell's configuration file, on an
    engine shell (see ``_serve_program``)."""
    import json
    import os
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.latent import LatentServed
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                  deepseek_v3_init)
    from jax.experimental.compilation_cache import compilation_cache
    sizes = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", config_file)))
    inf = sizes["serve"]["inference"]
    cfg = DeepseekV3Config.from_hf(
        sizes, n_routed_experts=sizes["n_routed_experts_published"],
        held=(0, sizes["n_routed_experts"]),
        vocab_rows_held=sizes["assumed"]["vocab_rows_held"], **overrides)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: deepseek_v3_init(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    spec = kv_cache.PagedKVCacheSpec(
        num_layers=cfg.num_hidden_layers, num_slots=inf["max_slots"],
        num_blocks=inf["num_blocks"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_heads=1, head_dim=cfg.latent_width,
        dtype=jnp.bfloat16,
        pools=LatentServed(cfg).cache_pools(inf["block_size"]))
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = cfg, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {"latent": one}
    S, J, C = spec.num_slots, spec.max_blocks_per_slot, eng.prefill_chunk
    pool = on_chip(jax.ShapeDtypeStruct(spec.pool_shapes["latent"],
                                        spec.dtype))
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, pool, i32(S + len(eng.served.counter_names)), i32(S),
            fresh(S), i32(S), i32(S, J), key, temp).compile()
        out["prefill_step"] = eng._build_prefill_step().lower(
            params, pool, i32(1, C), i32(1, J), i32(1), i32(1), i32(1), i32(),
            key, temp).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return spec, param_bytes, out


@pytest.fixture(scope="module")
def latent_programs(topo):
    return _latent_cell_programs(topo, "gigachat3.1-702b-a36b.json")


@pytest.fixture(scope="module")
def hyper_connected_programs(topo):
    """... for the cell whose blocks keep four residual streams (PR 41)."""
    return _latent_cell_programs(topo, "xing4.0-29b-a4b.json")


@pytest.mark.parametrize("program", ["decode_step", "prefill_step"])
def test_latent_serve_step_fits_and_updates_the_pool_in_place(
        latent_programs, program):
    """Weights 8.59 GB + latent pool 2.64 GB, the pool aliased to the
    output, next to no scratch, every kernel a TPU custom call, and no
    expert weight (1.4 GB a layer) sliced out of its stack and copied."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    spec, param_bytes, programs = latent_programs
    compiled = programs[program]
    assert abs(param_bytes - 8.585e9) < 0.01e9
    assert spec.nbytes() == 7168 * 64 * 5 * 1152
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= spec.nbytes()
    assert mem.temp_size_in_bytes < 256 * 2 ** 20, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes - param_bytes - spec.nbytes() \
        < 16 * 2 ** 20
    text = compiled.as_text()
    for kernel in ("_latent_attn_kernel", "_latent_write_kernel",
                   "_gswiglu_kernel"):
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel
    seen = ops_in_units_of(text, math.prod(spec.pool_shapes["latent"][2:]))
    assert not [(op, n) for op, n in seen if op not in _POOL_OPS_ALLOWED]
    one_layers_experts = 16 * 2048 * 7168
    assert not [(op, n) for op, n in ops_in_units_of(
        text, one_layers_experts) if op not in ("parameter", "bitcast",
                                                 "get-tuple-element")]


@pytest.mark.parametrize("program", ["decode_step", "prefill_step"])
def test_hyper_connected_serve_step_fits_beside_every_expert_and_row(
        hyper_connected_programs, program):
    """Weights 9.59 GB (64 of 64 experts, 131,072 vocabulary rows, the
    fp32 residual maps) + latent pool 5.44 GB, the pool aliased to the
    output; what the program needs beside them (the [256, 131072] fp32
    logits, four 3,584-wide residual streams) leaves it inside the chip's
    16 GiB; every kernel a TPU custom call; no layer's experts (1.41 GB)
    sliced out of their stack and copied; the maps' Sinkhorn iterations
    are traced (``hc`` in the op names)."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    spec, param_bytes, programs = hyper_connected_programs
    compiled = programs[program]
    assert abs(param_bytes - 9.594e9) < 0.01e9
    assert spec.nbytes() == 12288 * 64 * 6 * 1152
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= spec.nbytes()
    assert param_bytes + spec.nbytes() + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30, (mem.temp_size_in_bytes, mem.output_size_in_bytes)
    text = compiled.as_text()
    for kernel in ("_latent_attn_kernel", "_latent_write_kernel",
                   "_gswiglu_kernel"):
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel
    seen = ops_in_units_of(text, math.prod(spec.pool_shapes["latent"][2:]))
    assert not [(op, n) for op, n in seen if op not in _POOL_OPS_ALLOWED]
    # (the head's 131,072 x 3,584 happens to be two layers' worth of one
    # expert matrix: what a fusion does on it in place is the head's)
    one_layers_experts, head = 64 * 1024 * 3584, 131072 * 3584
    assert not [(op, n) for op, n in ops_in_units_of(
        text, one_layers_experts) if n != head and op not in (
            "parameter", "bitcast", "get-tuple-element")]
    assert "/hc/hc_maps" in text and "/hc/hc_post" in text


# ------------------------------------------------------------------ #
# The retention family (PR 34): the state-update kernel at the published
# head width, and the ENGINE's programs over the per-stream state pool at
# the benchmark cell's shape
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("streams", [32, 1])
def test_state_update_kernel_compiles_at_the_published_widths(streams,
                                                              one_chip,
                                                              as_tpu):
    from deepspeed_tpu.ops import power_retention as pr
    nKV, nH, Dh, L, B = 8, 40, 128, 4, 8
    tiles = dict(pr.state_tiles(nKV, Dh))
    text = _compile(
        lambda st, nm, layer, pages, q, k, v, lg: pr.state_update(
            st, nm, layer, pages, q, k, v, lg, eps=1e-6), one_chip,
        _sds((L, 1, B) + tiles["state"], jnp.float32),
        _sds((L, 1, B) + tiles["norm"], jnp.float32), _sds((), jnp.int32),
        _sds((1, streams), jnp.int32),
        _sds((1, streams, nH, Dh), jnp.bfloat16),
        _sds((1, streams, nKV, Dh), jnp.bfloat16),
        _sds((1, streams, nKV, Dh), jnp.bfloat16),
        _sds((1, streams, nKV), jnp.float32))
    assert "_state_update_kernel" in text


@pytest.fixture(scope="module")
def retention_programs(topo):
    """(spec, params bytes, {program: compiled}) of the engine's own step
    builders for the retention cell, on an engine shell (see
    ``_serve_program``)."""
    import json
    import os
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.retention import RetentionServed
    from deepspeed_tpu.models.brumby import BrumbyConfig, brumby_init
    from jax.experimental.compilation_cache import compilation_cache
    sizes = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "brumby-14b-base.json")))
    inf = sizes["serve"]["inference"]
    cfg = BrumbyConfig.from_hf(sizes)
    served = RetentionServed(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: brumby_init(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    spec = kv_cache.PagedKVCacheSpec(
        num_layers=served.cache_layers, num_slots=inf["max_slots"],
        num_blocks=inf["num_blocks"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_heads=served.cache_heads,
        head_dim=served.cache_row_width,
        pools=served.cache_pools(inf["block_size"]), per_stream=True,
        token_row_bytes=served.token_row_bytes)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = cfg, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {name: one for name in spec.pool_names}
    S, J, C = spec.num_slots, spec.max_blocks_per_slot, eng.prefill_chunk
    pools = [on_chip(jax.ShapeDtypeStruct(spec.pool_shapes[n],
                                          spec.pool_dtypes[n]))
             for n in spec.pool_names]
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, *pools, i32(S + len(eng.served.counter_names)), i32(S),
            fresh(S), i32(S), i32(S, J), key, temp).compile()
        out["prefill_step"] = eng._build_prefill_step().lower(
            params, *pools, i32(1, C), i32(1, J), i32(1), i32(1), i32(1),
            i32(), key, temp).compile()
        out["state_copy"] = eng._build_copy("state_copy", "state_copy").lower(
            *pools, i32(1), i32(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return spec, param_bytes, out


@pytest.mark.parametrize("program", ["decode_step", "prefill_step",
                                     "state_copy"])
def test_retention_programs_fit_and_rewrite_the_state_pool_in_place(
        retention_programs, program):
    """Weights 5.75 GB + a 7.16 GB pool of 52 pages, a page one block wide,
    the pools aliased to the outputs, scratch of a page or less: decode
    hands the pool to the aliased kernel and to nothing else; prefill and
    the page copy touch it through ONE in-place ``dynamic-update-slice``
    each (never a select over the pool)."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    spec, param_bytes, programs = retention_programs
    compiled = programs[program]
    assert abs(param_bytes - 5.754e9) < 0.01e9
    assert spec.max_blocks_per_slot == 1 and spec.page_tokens == 8400
    assert spec.nbytes() == 52 * 4 * 8 * (8320 + 80) * 128 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= spec.nbytes()
    assert mem.temp_size_in_bytes < 160 * 2 ** 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    seen = ops_in_units_of(text, math.prod(spec.pool_shapes["state"][1:]))
    in_place = {"dynamic-update-slice", "fusion"} \
        if program != "decode_step" else set()
    assert not [(op, n) for op, n in seen
                if op not in _POOL_OPS_ALLOWED | in_place]
    assert "select(" not in "".join(
        l for l in text.splitlines() if "f32[4,1,52," in l.split(" = ")[0])
    if program == "decode_step":
        calls = [line for line in text.splitlines()
                 if "%_state_update_kernel" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls)


@pytest.mark.parametrize("shape", [(1, 8, 5, 128), (512, 5, 128)])
def test_pair_products_compile_alone_for_v5e(shape, one_chip,
                                             no_persistent_cache):
    """The feature map as a program of its own (what an eager call
    makes): a plain ``jnp.roll`` by half the lanes over a group's five
    heads aborts the TPU compiler; ``power_retention._rotated`` does
    not."""
    from deepspeed_tpu.ops import power_retention as pr
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    jax.jit(pr.pair_products).lower(x).compile()
    jax.jit(pr.key_features).lower(x).compile()


def test_pair_tensor_of_a_page_compiles_for_v5e(one_chip,
                                                no_persistent_cache):
    """What the benchmark reads a page through (a layer's state and
    normaliser as held, at the published widths): two scatters into 129
    faces of 128 x 128, well inside a page's bytes."""
    from deepspeed_tpu.ops import power_retention as pr
    tiles = dict(pr.state_tiles(8, 128))
    S, z = (jax.ShapeDtypeStruct(tiles[n], jnp.float32, sharding=one_chip)
            for n in ("state", "norm"))
    compiled = jax.jit(pr.pair_tensor).lower(S, z).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 8 * 129 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 3 * mem.output_size_in_bytes


# ------------------------------------------------------------------ #
# The lfm2_moe family (PR 45): the ENGINE's programs over TWO KINDS of
# cache — K/V pages of 4 query heads a K/V head on heads of 64 and a conv
# state a stream — at the benchmark cell's shape
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def mixed_kind_programs(topo):
    """(specs, params bytes, {program: compiled}) of the engine's own step
    builders for ``perfbench/configs/lfm2-24b-a2b.json`` on an engine shell
    (see ``_serve_program``): ``decode_step``, ``prefill_step`` at both of
    ``prefill_widths`` and the page copy."""
    import json
    import os
    from types import SimpleNamespace
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import (InferenceEngine,
                                                prefill_widths)
    from deepspeed_tpu.inference.served import served_model
    from deepspeed_tpu.models.lfm2 import Lfm2Config, lfm2_init
    from jax.experimental.compilation_cache import compilation_cache
    sizes = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "lfm2-24b-a2b.json")))
    inf = sizes["serve"]["inference"]
    cfg = Lfm2Config.from_hf(
        sizes, initializer_range=sizes["assumed"]["initializer_range"])
    served = served_model(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: lfm2_init(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    specs = kv_cache.class_specs(
        served.cache_classes, inf["num_blocks"], rows=inf["prefill_chunk"],
        of_class=lambda c: served.class_geometry(c, inf["block_size"]),
        num_slots=inf["max_slots"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_groups=1, dtype=jnp.bfloat16)
    served.table_widths = tuple(sp.max_blocks_per_slot for sp in specs)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = served, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {n: one for sp in specs for n in sp.pool_names}
    eng.allocator = SimpleNamespace(copy_pools=specs[-1].pool_names)
    pools = [on_chip(jax.ShapeDtypeStruct(sp.pool_shapes[n], sp.dtype))
             for sp in specs for n in sp.pool_names]
    S, J = inf["max_slots"], sum(served.table_widths)
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, *pools, i32(S + len(served.counter_names)), i32(S),
            fresh(S), i32(S), i32(S, J), key, temp).compile()
        for C in prefill_widths(inf["prefill_chunk"], inf["block_size"]):
            # (+ the snapshot's row and page: the program freezes it)
            out[f"prefill_step.{C}"] = eng._build_prefill_step().lower(
                params, *pools, i32(1, C), i32(1, J), i32(1), i32(1), i32(1),
                i32(1), i32(1), i32(), key, temp).compile()
        out["state_copy"] = eng._build_copy("state_copy", "state_copy").lower(
            *pools, i32(1), i32(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return specs, param_bytes, out


@pytest.mark.parametrize("program", ["decode_step", "prefill_step.256",
                                     "prefill_step.512"])
def test_mixed_kind_serve_step_fits_and_updates_both_kinds_in_place(
        mixed_kind_programs, program):
    """Weights 10.36 GB (64 of 64 experts, 65,536 vocabulary rows, tied
    head) + K/V pools 4.29 GB + 512 conv pages of 57,344 B, every pool
    aliased to its output, scratch far under what is left of the chip's 16
    GiB; the attend (4 query heads a K/V head, heads of 64: two positions a
    lane row), the row write and the grouped expert product TPU custom
    calls; no K/V-pool-sized op, and no layer's experts (0.6 GB) copied."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    specs, param_bytes, programs = mixed_kind_programs
    full, conv = specs
    compiled = programs[program]
    assert abs(param_bytes - 10.356e9) < 0.01e9
    assert full.nbytes() == 2 * 16384 * 64 * 2048
    assert conv.block_nbytes() == 7 * 2 * 2048 * 2 and conv.page_tokens == 14
    assert conv.pool_shapes == {"conv.conv": (7, 1, 512, 1, 32, 128)}
    pool_bytes = full.nbytes() + conv.nbytes()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 128 * 2 ** 20, mem.temp_size_in_bytes
    assert param_bytes + pool_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30
    text = compiled.as_text()
    for kernel in ("_pattn_kernel", "_kv_write_kernel", "_gswiglu_kernel"):
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel
    seen = ops_in_units_of(text, math.prod(full.pool_shapes["k.full"][2:]))
    assert not [(op, n) for op, n in seen if op not in _POOL_OPS_ALLOWED]
    one_layers_experts = 64 * 1536 * 2048
    assert not [(op, n) for op, n in ops_in_units_of(
        text, one_layers_experts) if op not in ("parameter", "bitcast",
                                                 "get-tuple-element")]
    assert "/conv/conv_mix" in text and "/attn/attend_full" in text
    # decode: a conv layer's rows go through the pages by one kernel
    calls = _filter_rows_calls(text)
    assert len(calls) == (7 if program == "decode_step" else 0)
    assert all("/conv/conv_mix/" in c for c in calls)


def test_the_page_copy_of_a_mixed_model_leaves_the_kv_pools_alone(
        mixed_kind_programs):
    """``state_copy`` copies a page in the conv class's pool and hands the
    K/V pools through where they lie: a page id means nothing there."""
    specs, _, programs = mixed_kind_programs
    full, conv = specs
    compiled = programs["state_copy"]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= full.nbytes() + conv.nbytes()
    assert mem.temp_size_in_bytes < 2 ** 20
    text = compiled.as_text()
    touched = [line for line in text.splitlines()
               if " = " in line
               and "bf16[2,1,16384,8,32,128]" in line.split(" = ")[1][:40]
               and " parameter(" not in line and "tuple(" not in line]
    assert not touched, touched[:3]
    assert "dynamic-update-slice" in text


# ------------------------------------------------------------------ #
# The falcon_h1 family (PR 48): the ENGINE's programs over TWO KINDS of
# cache in EVERY layer — K/V pages of 5 query heads a K/V head and an fp32
# state a stream — at the benchmark cell's shape
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("streams", [128, 1])
def test_ssm_state_update_kernel_compiles_at_the_published_widths(
        streams, one_chip, as_tpu):
    """32 heads x [256, 128] fp32 a page and layer, 16 heads (one group) a
    grid step, the pool aliased in and out."""
    from deepspeed_tpu.ops import ssm_scan
    S = streams
    pool = jax.ShapeDtypeStruct((4, 1, 8, 32, 256, 128), jnp.float32,
                                sharding=one_chip)
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,   # noqa: E731
                                        sharding=one_chip)
    compiled = jax.jit(
        lambda pool, pages, x, B, C, dt, da: ssm_scan.state_update(
            pool, 2, pages, x, B, C, dt, da), donate_argnums=0).lower(
        pool, jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=one_chip),
        f(1, S, 32, 128), f(1, S, 2, 256), f(1, S, 2, 256), f(1, S, 32),
        f(1, S, 32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "_ssm_state_update_kernel" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 4 * 8 * 32 * 256 * 128 * 4


@pytest.fixture(scope="module")
def both_kinds_in_a_layer_programs(topo):
    """(specs, params bytes, {program: compiled}) of the engine's own step
    builders for ``perfbench/configs/falcon-h1-34b.json`` on an engine shell
    (see ``_serve_program``): ``decode_step``, ``prefill_step`` at both of
    ``prefill_widths`` and the page copy."""
    import json
    import os
    from types import SimpleNamespace
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import (InferenceEngine,
                                                prefill_widths)
    from deepspeed_tpu.inference.served import served_model
    from deepspeed_tpu.models.falcon_h1 import (FalconH1Config,
                                                falcon_h1_init)
    from jax.experimental.compilation_cache import compilation_cache
    sizes = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "falcon-h1-34b.json")))
    inf = sizes["serve"]["inference"]
    cfg = FalconH1Config.from_hf(sizes)
    served = served_model(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: falcon_h1_init(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    specs = kv_cache.class_specs(
        served.cache_classes, inf["num_blocks"], rows=inf["prefill_chunk"],
        of_class=lambda c: served.class_geometry(c, inf["block_size"]),
        num_slots=inf["max_slots"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_groups=1, dtype=jnp.bfloat16)
    served.table_widths = tuple(sp.max_blocks_per_slot for sp in specs)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = served, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {n: one for sp in specs for n in sp.pool_names}
    eng.allocator = SimpleNamespace(copy_pools=specs[-1].pool_names)
    pools = [on_chip(jax.ShapeDtypeStruct(sp.pool_shapes[n],
                                          sp.pool_dtypes[n]))
             for sp in specs for n in sp.pool_names]
    S, J = inf["max_slots"], sum(served.table_widths)
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, *pools, i32(S), i32(S), fresh(S), i32(S), i32(S, J),
            key, temp).compile()
        for C in prefill_widths(inf["prefill_chunk"], inf["block_size"]):
            # (+ the snapshot's row and page: the program freezes it)
            out[f"prefill_step.{C}"] = eng._build_prefill_step().lower(
                params, *pools, i32(1, C), i32(1, J), i32(1), i32(1), i32(1),
                i32(1), i32(1), i32(), key, temp).compile()
        out["state_copy"] = eng._build_copy("state_copy", "state_copy").lower(
            *pools, i32(1), i32(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return specs, param_bytes, out


@pytest.mark.parametrize("program", ["decode_step", "prefill_step.256",
                                     "prefill_step.512", "state_copy"])
def test_both_kinds_in_a_layer_fit_and_are_updated_in_place(
        both_kinds_in_a_layer_programs, program):
    """Weights 8.79 GB (4 layers of 430.1 M, 261,120 rows of embedding and
    of untied head) + K/V pools 1.07 GB (bf16) + 184 state pages of 16.9 MB
    (fp32 state, bf16 filter rows): every pool aliased to its output,
    scratch inside what is left of the chip's 16 GiB; the attend (5 query
    heads a K/V head), the row write and, in decode, the state update are
    TPU custom calls; no program holds an op the size of a pool."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    specs, param_bytes, programs = both_kinds_in_a_layer_programs
    full, state = specs
    compiled = programs[program]
    assert abs(param_bytes - 8.794e9) < 0.01e9
    assert full.nbytes() == 2 * 2048 * 4 * 4 * 64 * 128 * 2
    assert state.block_nbytes() == 4 * (32 * 256 * 128 * 4 + 3 * 5120 * 2)
    # (a page is 2,063 tokens of this model's K/V rows; beside the pages a
    # prompt that adds one prefill program's rows leaves a snapshot)
    assert state.token_row_bytes == 2048 and state.page_tokens == 512
    assert state.pool_shapes == {"ssm.state": (4, 1, 184, 32, 256, 128),
                                 "conv.state": (4, 1, 184, 1, 120, 128)}
    pool_bytes = full.nbytes() + state.nbytes()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    if program == "state_copy":
        assert mem.temp_size_in_bytes < 64 * 2 ** 20
        return
    assert param_bytes + pool_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30, mem
    text = compiled.as_text()
    kernels = [_ATTEND_KERNEL[program.split(".")[0]], "_kv_write_kernel"]
    if program == "decode_step":
        kernels.append("_ssm_state_update_kernel")
    for kernel in kernels:
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel
    # (a chunk's state goes back into its page by a dynamic-update-slice
    # of the donated pool, fused with the page's read: in place, as the
    # retention family's does)
    in_place = {"dynamic-update-slice", "fusion"} \
        if program != "decode_step" else set()
    for pool in ("k.full", "ssm.state"):
        spec = full if pool == "k.full" else state
        seen = ops_in_units_of(text, math.prod(spec.pool_shapes[pool][2:]))
        assert not [(op, n) for op, n in seen
                    if op not in _POOL_OPS_ALLOWED | in_place], pool
    assert "/attn/attend_full" in text and "/ssm/ssm_conv" in text
    assert ("/ssm/ssm_state_update" if program == "decode_step"
            else "/ssm/ssm_chunk_scan") in text
    # 5,120 filter channels are 40 sublane rows of bf16 a held row, two and
    # a half tiles: ``ops.filter_rows.takes`` leaves every program's rows on
    # the plain lines
    assert not _filter_rows_calls(text)


# ------------------------------------------------------------------ #
# A decode step's short-filter rows rewritten in place (PR 53)
# ------------------------------------------------------------------ #
def _filter_rows_calls(text):
    """The compiled program's ``_filter_rows_kernel`` custom calls."""
    calls = [line for line in text.splitlines()
             if "%_filter_rows_kernel" in line.split(" = ")[0]
             and " custom-call(" in line]
    assert all("tpu_custom_call" in c for c in calls)
    return calls


@pytest.mark.parametrize("layers, pages, streams, tile_rows, held", [
    (6, 480, 256, 288, 3),          # kimi-linear: 96 sublane rows a held row
    (7, 512, 128, 32, 2),           # lfm2: 16
])
def test_filter_rows_kernel_compiles_at_the_published_widths(
        layers, pages, streams, tile_rows, held, one_chip, as_tpu):
    """Copies with a slice of the pipelined output block as their VMEM side,
    the pool in HBM aliased in and out; the shape rule takes both tiles and
    leaves falcon-h1's (40 rows of bf16 a held row) alone."""
    from deepspeed_tpu.ops import filter_rows as in_place
    C = tile_rows * 128 // held
    shape = (layers, 1, pages, 1, tile_rows, 128)
    assert in_place.takes(shape, jnp.bfloat16, held, jnp.bfloat16)
    assert not in_place.takes((4, 1, 184, 1, 120, 128), jnp.bfloat16, 3,
                              jnp.bfloat16)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(    # noqa: E731
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda pool, pages_, carried, new: in_place.shift_rows(
            pool, 2, pages_, carried, new, held=held),
        donate_argnums=0).lower(
        sds(shape, jnp.bfloat16), sds((1, streams), jnp.int32),
        sds((1, streams), jnp.bool_),
        sds((1, streams, C), jnp.bfloat16)).compile()
    assert len(_filter_rows_calls(compiled.as_text())) == 1
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= math.prod(shape) * 2


# ------------------------------------------------------------------ #
# The kimi_linear family (PR 52): the delta-rule decode kernel and the
# ENGINE's programs over a LATENT class (2 layers) beside a per-stream class
# (6 KDA layers: an fp32 state + bf16 filter rows) at the benchmark cell's
# shape
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("streams", [256, 1])
def test_kda_state_update_kernel_compiles_at_the_published_widths(
        streams, one_chip, as_tpu):
    """32 heads x [128, 128] fp32 a page and layer, 16 heads (1 MiB) a grid
    step, the pool aliased in and out."""
    from deepspeed_tpu.ops import kda
    S = streams
    assert kda.tile_heads(32, 128, 128) == 16
    pool = jax.ShapeDtypeStruct((6, 1, 8, 32, 128, 128), jnp.float32,
                                sharding=one_chip)
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,   # noqa: E731
                                        sharding=one_chip)
    compiled = jax.jit(
        lambda pool, pages, q, k, v, g, beta: kda.state_update(
            pool, 2, pages, q, k, v, g, beta), donate_argnums=0).lower(
        pool, jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=one_chip),
        f(1, S, 32, 128), f(1, S, 32, 128), f(1, S, 32, 128),
        f(1, S, 32, 128), f(1, S, 32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "_kda_state_update_kernel" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 6 * 8 * 32 * 128 * 128 * 4


def _programs_beside_a_frozen_state(topo, inf, cfg, init_fn):
    """(specs, params bytes, {program: compiled}) of the engine's own step
    builders on an engine shell (see ``_serve_program``) for a model whose
    LAST class is a per-stream state the chunk program freezes: ``decode_step``,
    ``prefill_step`` at both of ``prefill_widths`` and the page copy, under
    the configuration file's ``serve.inference`` (``inf``)."""
    from types import SimpleNamespace
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import (InferenceEngine,
                                                prefill_widths)
    from deepspeed_tpu.inference.served import served_model
    from jax.experimental.compilation_cache import compilation_cache
    served = served_model(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: init_fn(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    specs = kv_cache.class_specs(
        served.cache_classes, inf["num_blocks"], rows=inf["prefill_chunk"],
        of_class=lambda c: served.class_geometry(c, inf["block_size"]),
        num_slots=inf["max_slots"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_groups=1, dtype=jnp.bfloat16)
    served.table_widths = tuple(sp.max_blocks_per_slot for sp in specs)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = served, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {n: one for sp in specs for n in sp.pool_names}
    eng.allocator = SimpleNamespace(copy_pools=specs[-1].pool_names)
    pools = [on_chip(jax.ShapeDtypeStruct(sp.pool_shapes[n],
                                          sp.pool_dtypes[n]))
             for sp in specs for n in sp.pool_names]
    S, J = inf["max_slots"], sum(served.table_widths)
    n_ctr = len(served.counter_names)
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, *pools, i32(S + n_ctr), i32(S), fresh(S), i32(S),
            i32(S, J), key, temp).compile()
        for C in prefill_widths(inf["prefill_chunk"], inf["block_size"]):
            # (+ the snapshot's row and page: the program freezes it)
            out[f"prefill_step.{C}"] = eng._build_prefill_step().lower(
                params, *pools, i32(1, C), i32(1, J), i32(1), i32(1), i32(1),
                i32(1), i32(1), i32(), key, temp).compile()
        out["state_copy"] = eng._build_copy("state_copy", "state_copy").lower(
            *pools, i32(1), i32(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return specs, param_bytes, out


def _cell_sizes(name: str) -> dict:
    """``perfbench/configs/<name>.json`` (and the checkout on the path, for
    the cell's runner)."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    return json.load(open(os.path.join(root, "perfbench", "configs",
                                       name + ".json")))


@pytest.fixture(scope="module")
def latent_beside_state_programs(topo):
    """``_programs_beside_a_frozen_state`` for
    ``perfbench/configs/kimi-linear-48b-a3b.json``."""
    from deepspeed_tpu.models.kimi_linear import kimi_linear_init
    sizes = _cell_sizes("kimi-linear-48b-a3b")
    from perfbench.runners import longgen
    return _programs_beside_a_frozen_state(
        topo, sizes["serve"]["inference"], longgen.model_config(sizes),
        kimi_linear_init)


@pytest.mark.parametrize("program", ["decode_step", "prefill_step.256",
                                     "prefill_step.512", "state_copy"])
def test_latent_beside_state_fit_and_are_updated_in_place(
        latent_beside_state_programs, program):
    """Weights 2.60 GB (one dense KDA layer, five KDA and two latent expert
    layers of 16 held experts, 20,480 rows of embedding and of untied head)
    + the latent pool (2 layers x 1,152 B a token) + the state pages (6
    layers x (2 MiB fp32 + 72 KiB bf16 filter rows)): every pool aliased to
    its output, scratch inside what is left of the chip's 16 GiB; the latent
    attend, the row write, the grouped expert product and, in decode, the
    delta-rule update are TPU custom calls; no program holds an op the size
    of a pool."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    specs, param_bytes, programs = latent_beside_state_programs
    latent, state = specs
    compiled = programs[program]
    assert abs(param_bytes - 2.600e9) < 0.01e9, param_bytes
    assert (latent.num_layers, state.num_layers) == (2, 6)
    assert latent.block_nbytes() == 2 * 128 * 576 * 2
    assert state.block_nbytes() == 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) \
        == 13025280
    # (a page is 5,654 tokens of this model's latent rows; beside the blocks
    # a prompt that adds one prefill program's rows leaves a snapshot)
    assert state.token_row_bytes == 384 and state.page_tokens == 512
    B = state.num_blocks
    assert state.pool_shapes == {"state.state": (6, 1, B, 32, 128, 128),
                                 "conv.state": (6, 1, B, 1, 288, 128)}
    assert state.pool_dtypes == {"state.state": jnp.float32,
                                 "conv.state": jnp.bfloat16}
    assert latent.max_blocks_per_slot == 452
    pool_bytes = latent.nbytes() + state.nbytes()
    assert 0.60 < (param_bytes + pool_bytes) / 2 ** 34 < 0.85
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    if program == "state_copy":
        assert mem.temp_size_in_bytes < 64 * 2 ** 20
        return
    assert param_bytes + pool_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30, mem
    text = compiled.as_text()
    kernels = ["_latent_attn_kernel", "_latent_write_kernel",
               "_gswiglu_kernel"]
    if program == "decode_step":
        kernels.append("_kda_state_update_kernel")
    for kernel in kernels:
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel
    # (a chunk's state goes back into its page by a dynamic-update-slice of
    # the donated pool, fused with the page's read: in place)
    in_place = {"dynamic-update-slice", "fusion"} \
        if program != "decode_step" else set()
    for spec, pool in ((latent, "latent.latent"), (state, "state.state")):
        seen = ops_in_units_of(text, math.prod(spec.pool_shapes[pool][2:]))
        assert not [(op, n) for op, n in seen
                    if op not in _POOL_OPS_ALLOWED | in_place], pool
    assert "/attn/attend" in text and "/attn/kda_conv" in text
    assert ("/attn/kda_update" if program == "decode_step"
            else "/attn/kda_chunk") in text
    # decode: a KDA layer's filter rows go through the pages by one kernel,
    # and nothing gathers or scatters them; a chunk keeps the plain lines
    calls = _filter_rows_calls(text)
    assert len(calls) == (6 if program == "decode_step" else 0)
    assert all("/attn/kda_conv/" in c for c in calls)
    if program == "decode_step":
        assert not [line for line in text.splitlines() if "/kda_conv/" in line
                    and re.search(r" (gather|scatter)\(", line)]


# ------------------------------------------------------------------ #
# The solar_open2 family (PR 64): K/V pages under an output gate beside
# delta-rule states of 64 heads, at the agent-sessions cell's shapes
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def pages_beside_delta_state_programs(topo):
    """``_programs_beside_a_frozen_state`` for
    ``perfbench/configs/solar-open2-250b.json``."""
    from deepspeed_tpu.models.solar_open2 import solar_open2_init
    sizes = _cell_sizes("solar-open2-250b")
    from perfbench.runners import agent_sessions
    return _programs_beside_a_frozen_state(
        topo, sizes["serve"]["inference"],
        agent_sessions.model_config(sizes), solar_open2_init)


@pytest.mark.parametrize("program", ["decode_step", "prefill_step.256",
                                     "prefill_step.512", "state_copy"])
def test_pages_beside_delta_states_fit_and_are_updated_in_place(
        pages_beside_delta_state_programs, program):
    """Weights 6.62 GB (one gated grouped-query layer and three KDA layers of
    64 heads, four expert layers of 40 held experts, 24,576 rows of embedding
    and of untied head) + the K/V pool (one layer x 4,096 B a token) + the
    state pages (3 layers x (4 MiB fp32 + 144 KiB bf16 filter rows)): every
    pool aliased to its output, scratch inside what is left of the chip's 16
    GiB; the attend — whose table of 64 streams x 1,664 blocks of 128 is 426
    KB of SMEM's 1 MiB — the row write, the grouped expert product and, in
    decode, the delta-rule update are TPU custom calls; no program holds an
    op the size of a pool."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    specs, param_bytes, programs = pages_beside_delta_state_programs
    full, state = specs
    compiled = programs[program]
    assert param_bytes == 6617348608, param_bytes
    assert (full.num_layers, state.num_layers) == (1, 3)
    assert full.block_nbytes() == 2 * 128 * 8 * 128 * 2 == 524288
    assert state.block_nbytes() == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2) \
        == 13025280
    # (a page is 3,180 tokens of this model's K/V rows; beside the blocks a
    # prompt that adds one prefill program's rows leaves a snapshot)
    assert state.token_row_bytes == -(-4096 // 3) and state.page_tokens == 512
    B = state.num_blocks
    assert state.pool_shapes == {"state.state": (3, 1, B, 64, 128, 128),
                                 "conv.state": (3, 1, B, 1, 576, 128)}
    assert state.pool_dtypes == {"state.state": jnp.float32,
                                 "conv.state": jnp.bfloat16}
    assert full.max_blocks_per_slot == 1664
    assert 64 * 1664 * 4 < 2 ** 19               # the table in SMEM
    pool_bytes = full.nbytes() + state.nbytes()
    assert 0.75 < (param_bytes + pool_bytes) / 2 ** 34 < 0.90
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    if program == "state_copy":
        assert mem.temp_size_in_bytes < 64 * 2 ** 20
        return
    assert param_bytes + pool_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30, mem
    text = compiled.as_text()
    kernels = [_ATTEND_KERNEL[program.split(".")[0]], "_kv_write_kernel",
               "_gswiglu_kernel"]
    if program == "decode_step":
        kernels.append("_kda_state_update_kernel")
    for kernel in kernels:
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel
    in_place = {"dynamic-update-slice", "fusion"} \
        if program != "decode_step" else set()
    for spec, pool in ((full, "k.full"), (full, "v.full"),
                       (state, "state.state")):
        seen = ops_in_units_of(text, math.prod(spec.pool_shapes[pool][2:]))
        assert not [(op, n) for op, n in seen
                    if op not in _POOL_OPS_ALLOWED | in_place], pool
    assert "/attn/attend_full" in text and "/attn/attn_gate" in text \
        and "/attn/kda_conv" in text
    assert ("/attn/kda_update" if program == "decode_step"
            else "/attn/kda_chunk") in text
    # decode: a KDA layer's filter rows (192 sublane rows a held row) go
    # through the pages by one kernel
    calls = _filter_rows_calls(text)
    assert len(calls) == (3 if program == "decode_step" else 0)


# ------------------------------------------------------------------ #
# The smallthinker family (PR 54): the ENGINE's programs over two classes
# of K/V pages at 7 query heads a K/V head, every layer an expert layer
# routed ahead of its attention, ReLU-gated experts of 768, at the
# benchmark cell's shape
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("cls,blocks,layers,J,reach", [
    ("full", 10240, 2, 256, None), ("window", 4864, 6, 73, 4096)])
@pytest.mark.parametrize("K,Q,tiles", [(1, 64, (4, 16)), (64, 8, (4, 8))],
                         ids=["decode_64_streams", "prefill_run_512_rows"])
def test_paged_attention_at_the_paste_cells_shapes(K, Q, tiles, cls, blocks,
                                                   layers, J, reach,
                                                   one_chip, as_tpu):
    """The attend ALONE at `serve.smallthinker-21b-a3b.paste-over`'s shapes
    (4 K/V heads of 128 under 28 query heads: SEVEN query rows a K/V head
    in decode, odd against the sublane count; 448 a prefill run of 64,
    which takes the chunk body in two bands of 224 rows (PR 65);
    blocks of 64, bf16; the full class's 10,240 blocks x 2 layers behind a
    table of 256 and the window class's 4,864 x 6 behind a ring of 73):
    scoped VMEM asked and kept at the default 16 MiB, and nothing but
    parameters, bitcasts and the kernel holds a pool or a layer of it."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    from deepspeed_tpu.inference.kv_pages import attend_rows
    from deepspeed_tpu.ops import paged_attention as pa
    nKV, grp, Dh, bs = 4, 7, 128, 64
    assert attend_rows(512, grp) == 64 and attend_rows(1, grp) == 1
    assert pa._tile_rule(grp * K, nKV, Dh, bs, J, 2, 2) == tiles
    kernel = _attend_kernel_and_its_vmem(grp * K, tiles, Dh, bs)
    shape = (layers, 1, blocks, nKV, bs, Dh)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in (((1, Q, K, nKV * grp, Dh), jnp.bfloat16),
                           ((), jnp.int32), ((1, Q, J), jnp.int32),
                           ((1, Q, K), jnp.int32))]

    def attend(q, pk, pv, layer, bt, pos):
        plan = pa.attend_plan(bt, pos, pk, Dh, reach=reach, group=grp)
        return pa.paged_attention(q, pk, pv, layer, plan=plan,
                                  scale=Dh ** -0.5)
    compiled = jax.jit(attend).lower(args[0], pool, pool,
                                     *args[1:]).compile()
    text = compiled.as_text()
    seen = ops_in_units_of(text, math.prod(shape[2:]))
    assert {op for op, _ in seen} <= {"parameter", "bitcast",
                                      "custom-call"}, seen
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20
    calls = [line for line in text.splitlines()
             if f"%{kernel}" in line.split(" = ")[0]
             and " custom-call(" in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert f'"size":"{pa._VMEM_LIMIT}"' in calls[0]


@pytest.mark.parametrize("tokens", [64, 512])
def test_grouped_reglu_compiles_at_the_published_widths(tokens, one_chip,
                                                        as_tpu):
    """64 experts of [768, 2560]: one F tile (6 x 768 x 2560 x 2 B = 23.6 MB
    of the 48 MiB rule); a decode iteration's 6 rows an expert in tiles of
    16, a chunk's 48 in tiles of 128; the worst routing's row buffer."""
    from deepspeed_tpu.models.smallthinker import SmallthinkerConfig
    from deepspeed_tpu.moe import share
    from deepspeed_tpu.ops import grouped_gemm as gg
    cfg = SmallthinkerConfig()
    assert gg._swiglu_f_tile(768, 2560, 2) == 768
    tm = share._row_tile(tokens, cfg.routing)
    assert tm == (16 if tokens == 64 else 128)
    M = -(-tokens * 6 // tm) * tm + 64 * tm          # the worst routing
    w = _sds((64, 768, 2560), jnp.bfloat16)
    text = _compile_grouped(one_chip, tokens, 2560, M, tm, w, act="relu")
    assert "_greglu_kernel" in text and "_gswiglu_kernel" not in text


@pytest.fixture(scope="module")
def paste_cell_programs(topo):
    """(specs, params bytes, {program: compiled}) of the engine's own step
    builders for ``perfbench/configs/smallthinker-21b-a3b.json`` on an
    engine shell (see ``_serve_program``): ``decode_step`` and
    ``prefill_step`` at every width of ``prefill_widths``."""
    import json
    import os
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import (InferenceEngine,
                                                prefill_widths)
    from deepspeed_tpu.inference.served import served_model
    from deepspeed_tpu.models.smallthinker import (SmallthinkerConfig,
                                                   smallthinker_init)
    from jax.experimental.compilation_cache import compilation_cache
    sizes = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "smallthinker-21b-a3b.json")))
    inf = sizes["serve"]["inference"]
    cfg = SmallthinkerConfig.from_hf(sizes)
    served = served_model(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: smallthinker_init(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    specs = kv_cache.class_specs(
        served.cache_classes, inf["num_blocks"], rows=inf["prefill_chunk"],
        of_class=lambda c: served.class_geometry(c, inf["block_size"]),
        num_slots=inf["max_slots"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_groups=1, dtype=jnp.bfloat16)
    served.table_widths = tuple(sp.max_blocks_per_slot for sp in specs)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = served, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {n: one for sp in specs for n in sp.pool_names}
    pools = [on_chip(jax.ShapeDtypeStruct(sp.pool_shapes[n], sp.dtype))
             for sp in specs for n in sp.pool_names]
    S, J = inf["max_slots"], sum(served.table_widths)
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, *pools, i32(S + len(served.counter_names)), i32(S),
            fresh(S), i32(S), i32(S, J), key, temp).compile()
        for C in prefill_widths(inf["prefill_chunk"], inf["block_size"]):
            out[f"prefill_step.{C}"] = eng._build_prefill_step().lower(
                params, *pools, i32(1, C), i32(1, J), i32(1), i32(1), i32(1),
                i32(), key, temp).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return specs, param_bytes, out


@pytest.mark.parametrize("program", ["decode_step", "prefill_step.256",
                                     "prefill_step.512"])
def test_paste_cell_serve_step_fits_and_updates_both_classes_in_place(
        paste_cell_programs, program):
    """Weights 7.93 GB (64 of 64 experts in all 8 layers, 151,936 vocabulary
    rows, untied head) + K/V pools 6.51 GB (full: 10,240 blocks x 2 layers
    behind a table of 256; window: 4,864 x 6 behind a ring of 73), every
    pool aliased to its output, scratch far under what is left of the
    chip's 16 GiB; the attend at 7 query heads a K/V head, the row write
    and the ReLU-gated grouped product TPU custom calls, eight of each a
    program; no K/V-pool-sized op and no layer's experts (0.75 GB) copied;
    the router's and the dispatch's ops named under ``moe`` in both."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    specs, param_bytes, programs = paste_cell_programs
    full, window = specs
    compiled = programs[program]
    assert param_bytes == 2 * 3_966_937_600
    assert (full.max_blocks_per_slot, window.max_blocks_per_slot) == (256, 73)
    assert full.nbytes() == 2 * 10240 * 64 * 2048
    assert window.nbytes() == 6 * 4864 * 64 * 2048
    assert window.block_nbytes() == 786_432
    pool_bytes = full.nbytes() + window.nbytes()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 640 * 2 ** 20, mem.temp_size_in_bytes
    assert param_bytes + pool_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30
    text = compiled.as_text()
    for kernel in (_ATTEND_KERNEL[program.split(".")[0]], "_kv_write_kernel",
                   "_greglu_kernel"):
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert len(calls) == 8 \
            and all("tpu_custom_call" in c for c in calls), kernel
    assert "_gswiglu_kernel" not in text
    for sp in specs:
        seen = ops_in_units_of(
            text, math.prod(sp.pool_shapes["k." + sp.name][2:]))
        assert not [(op, n) for op, n in seen
                    if op not in _POOL_OPS_ALLOWED], (sp.name, seen)
    one_layers_experts = 64 * 768 * 2560
    assert not [(op, n) for op, n in ops_in_units_of(
        text, one_layers_experts) if op not in ("parameter", "bitcast",
                                                 "get-tuple-element")]
    for scope in ("/moe/router", "/moe/dispatch", "/moe/experts",
                  "/moe/combine", "/attn/attend_full", "/attn/attend_window",
                  "/attn/qkv_proj", "/attn/kv_write", "/attn/out_proj",
                  "/lm_head"):
        assert scope in text, scope


# ------------------------------------------------------------------ #
# A per-K/V-head table in the paged attend, a pool at another rate, several
# one-head groups a step of the state kernel: MiniCPM-SALA (PR 59) at the
# benchmark cell's shape
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("streams", [256, 1])
def test_state_update_of_one_head_groups_compiles_at_the_published_widths(
        streams, one_chip, as_tpu):
    """32 heads x [128, 128] fp32 a page and layer, every head a group of
    its own: all 32 a grid step (2 MB), B and C as columns of an [N, heads]
    block, the pool aliased in and out."""
    from deepspeed_tpu.ops import ssm_scan
    assert ssm_scan.tile_heads(32, 32, 128, 128) == 32
    assert ssm_scan.tile_heads(32, 2, 256, 128) == 16     # cell 9's: as was
    S = streams
    pool = jax.ShapeDtypeStruct((6, 1, 8, 32, 128, 128), jnp.float32,
                                sharding=one_chip)
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,   # noqa: E731
                                        sharding=one_chip)
    compiled = jax.jit(
        lambda pool, pages, x, B, C, dt, da: ssm_scan.state_update(
            pool, 2, pages, x, B, C, dt, da), donate_argnums=0).lower(
        pool, jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=one_chip),
        f(1, S, 32, 128), f(1, S, 32, 128), f(1, S, 32, 128), f(1, S, 32),
        f(1, S, 32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "_ssm_state_update_kernel" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 6 * 8 * 32 * 128 * 128 * 4


@pytest.mark.parametrize("streams", [256, 512], ids=["decode", "chunk"])
def test_paged_attention_under_a_per_head_plan_compiles(streams, one_chip,
                                                        as_tpu):
    """16 query heads a K/V head as 16 query rows of a (stream, K/V head)
    step that walks ITS up to 128 chosen blocks' one-head tiles: decode's 256
    streams, a chunk's 512 rows each a stream."""
    from deepspeed_tpu.ops import paged_attention as pa
    Q, nKV, J, D, B = streams, 2, 128, 128, 1024
    pool = _sds((2, 1, B, nKV, 64, D), jnp.bfloat16)

    def fn(q, k, v, chosen, count, fill):
        plan = pa.attend_plan(chosen, fill, k, D, group=16, count=count)
        return pa.paged_attention(q, k, v, 1, plan=plan, scale=D ** -0.5)
    text = _compile(fn, one_chip, _sds((1, Q, 1, 32, D), jnp.bfloat16), pool,
                    pool, _sds((1, Q, nKV, J), jnp.int32),
                    _sds((1, Q, nKV), jnp.int32), _sds((1, Q, 1), jnp.int32))
    assert "_pattn_kernel" in text
    assert pa._tile_rule(16, 1, D, 64, J, 2) == (1, 32)


@pytest.fixture(scope="module")
def sparse_beside_state_programs(topo):
    """(specs, params bytes, {program: compiled}) of the engine's own step
    builders for ``perfbench/configs/minicpm-sala.json`` on an engine shell
    (see ``_serve_program``)."""
    import json
    import os
    from types import SimpleNamespace
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import (InferenceEngine,
                                                prefill_widths)
    from deepspeed_tpu.inference.served import served_model
    from deepspeed_tpu.models.minicpm_sala import (MinicpmSalaConfig,
                                                   minicpm_sala_init)
    from jax.experimental.compilation_cache import compilation_cache
    sizes = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "minicpm-sala.json")))
    inf = sizes["serve"]["inference"]
    cfg = MinicpmSalaConfig.from_hf(sizes)
    assert cfg.vocab_rows == sizes["assumed"]["vocab_rows_held"]
    served = served_model(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: minicpm_sala_init(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    specs = kv_cache.class_specs(
        served.cache_classes, inf["num_blocks"], rows=inf["prefill_chunk"],
        of_class=lambda c: served.class_geometry(c, inf["block_size"]),
        num_slots=inf["max_slots"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_groups=1, dtype=jnp.bfloat16)
    served.table_widths = tuple(sp.max_blocks_per_slot for sp in specs)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = served, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {n: one for sp in specs for n in sp.pool_names}
    eng.allocator = SimpleNamespace(copy_pools=specs[-1].pool_names)
    pools = [on_chip(jax.ShapeDtypeStruct(sp.pool_shapes[n],
                                          sp.pool_dtypes[n]))
             for sp in specs for n in sp.pool_names]
    S, J = inf["max_slots"], sum(served.table_widths)
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, *pools, i32(S), i32(S), fresh(S), i32(S), i32(S, J),
            key, temp).compile()
        for C in prefill_widths(inf["prefill_chunk"], inf["block_size"]):
            out[f"prefill_step.{C}"] = eng._build_prefill_step().lower(
                params, *pools, i32(1, C), i32(1, J), i32(1), i32(1), i32(1),
                i32(1), i32(1), i32(), key, temp).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return specs, param_bytes, out


@pytest.mark.parametrize("program", ["decode_step", "prefill_step.256",
                                     "prefill_step.512"])
def test_sparse_beside_state_fit_and_are_updated_in_place(
        sparse_beside_state_programs, program):
    """Weights 5.64 GB + the sparse class's three pools 2.21 GB (K, V and
    the pooled keys at a sixteenth of their rate) + 288 state pages of 12.6
    MB, every pool aliased to its output, the rest inside the chip's 16 GiB
    beside a table 2,073 slots wide x 256 streams; the per-head attend, the
    row write and, in decode, the state update are TPU custom calls."""
    specs, param_bytes, programs = sparse_beside_state_programs
    sparse, state = specs
    compiled = programs[program]
    assert param_bytes == 2 * 2_820_741_888
    assert sparse.max_blocks_per_slot == 2072
    assert sparse.block_nbytes() == 135_168
    assert sparse.pool_shapes["ck.sparse"] == (2, 1, 16384, 2, 4, 128)
    assert state.block_nbytes() == 6 * 32 * 128 * 128 * 4
    assert state.pool_dtypes["state.state"] == jnp.float32
    pool_bytes = sparse.nbytes() + state.nbytes()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert param_bytes + pool_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30, mem
    text = compiled.as_text()
    kernels = ["_pattn_kernel", "_kv_write_kernel"]
    if program == "decode_step":
        kernels.append("_ssm_state_update_kernel")
    for kernel in kernels:
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert calls and all("tpu_custom_call" in c for c in calls), kernel
    for scope in ("/attn/select", "/attn/ck_write", "/attn/attend_sparse",
                  "/attn/la_proj", "/attn/la_gate_norm", "/attn/la_out",
                  "/attn/la_state_update" if program == "decode_step"
                  else "/attn/la_chunk", "/mlp", "/lm_head"):
        assert scope in text, scope


def test_the_decode_steps_selection_gathers_a_shared_prefix_once(
        sparse_beside_state_programs):
    """PR 63, at the benchmark cell's shape: the decode step's ``select``
    gathers pooled keys ``[blocks, 2, 4, 128]`` a TILE of 32 streams at a
    time — the 2,072 blocks of its sharing group's table row once, and ``32
    x 32`` blocks of its streams' own — in each of the two sparse layers;
    the gather of a batch of 32 streams' whole tables
    (``bf16[66304,2,4,128]``: 1.08 GB a layer) is gone, and the per-stream
    arm a ``cond`` keeps reads 16 streams' tables a step."""
    from deepspeed_tpu.ops import sparse_select
    text = sparse_beside_state_programs[2]["decode_step"].as_text()
    gathered = {}
    for line in text.splitlines():
        m = re.search(r" = bf16\[([\d,]+),2,4,128\]\S* gather\(", line)
        if m and "/attn/select/" in line:
            lead = tuple(int(d) for d in m.group(1).split(","))
            gathered[lead] = gathered.get(lead, 0) + 1
    tile = (sparse_select._TILE_STREAMS, sparse_select._TAIL_SLOTS)
    assert gathered == {(2072,): 2, tile: 2,
                        (sparse_select._BATCH_STREAMS, 2072): 2}, gathered


# ------------------------------------------------------------------ #
# A step that is a pass over a block of 4 rows a slot, written through the
# paged cache and attended under block-causal limits, the head over S x B
# rows: SDAR-30B-A3B (PR 62) at the benchmark cell's shape
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def block_step_programs(topo):
    """(spec, params bytes, {program: compiled}) of the engine's own step
    builders for ``perfbench/configs/sdar-30b-a3b-chat.json`` on an engine
    shell: ``decode_step`` (the block step) and ``prefill_step`` at every
    width of ``prefill_widths``."""
    import json
    import os
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.inference.engine import (InferenceEngine,
                                                prefill_widths)
    from deepspeed_tpu.inference.served import served_model
    from deepspeed_tpu.models.sdar import SdarConfig, sdar_init
    from jax.experimental.compilation_cache import compilation_cache
    sizes = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "configs", "sdar-30b-a3b-chat.json")))
    inf = sizes["serve"]["inference"]
    cfg = SdarConfig.from_hf(sizes, denoising_steps=2,
                             remasking="low_confidence_static")
    served = served_model(cfg)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)
    params = on_chip(jax.eval_shape(lambda k: sdar_init(k, cfg),
                                    jax.random.PRNGKey(0)))
    param_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    specs = kv_cache.class_specs(
        served.cache_classes, inf["num_blocks"], rows=inf["prefill_chunk"],
        of_class=lambda c: served.class_geometry(c, inf["block_size"]),
        num_slots=inf["max_slots"], block_size=inf["block_size"],
        max_len=inf["max_seq_len"], num_groups=1, dtype=jnp.bfloat16)
    served.table_widths = tuple(sp.max_blocks_per_slot for sp in specs)
    eng = object.__new__(InferenceEngine)
    eng.model_cfg, eng.dp, eng.sp, eng.mesh = served, 1, 1, None
    eng.paged_kernel, eng.quantize = True, "none"
    eng.prefill_chunk = inf["prefill_chunk"]
    eng._cache_sh = {n: one for sp in specs for n in sp.pool_names}
    pools = [on_chip(jax.ShapeDtypeStruct(sp.pool_shapes[n], sp.dtype))
             for sp in specs for n in sp.pool_names]
    S, J, B = inf["max_slots"], sum(served.table_widths), cfg.block_length
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
    fresh = lambda n: on_chip(jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    temp = on_chip(jax.ShapeDtypeStruct((), jnp.float32))
    mp = pytest.MonkeyPatch()
    prev = jax.config.jax_enable_compilation_cache
    out = {}
    try:
        mp.setattr(jax, "default_backend", lambda *a, **k: "tpu")
        mp.setenv("DS_AUTOTUNE", "0")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        out["decode_step"] = eng._build_decode_step().lower(
            params, *pools,
            i32(S * (2 * B + 1) + len(served.counter_names)),
            i32(S, B + 1),
            fresh(S), i32(S), i32(S, J), key, temp).compile()
        for C in prefill_widths(inf["prefill_chunk"], inf["block_size"]):
            out[f"prefill_step.{C}"] = eng._build_prefill_step().lower(
                params, *pools, i32(1, C), i32(1, J), i32(1), i32(1), i32(1),
                i32(), key, temp).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
        mp.undo()
    return specs[0], param_bytes, out


@pytest.mark.parametrize("program", ["decode_step", "prefill_step.256",
                                     "prefill_step.512"])
def test_block_step_fits_and_writes_its_rows_in_place(block_step_programs,
                                                      program):
    """Weights 8.72 GB (128 of 128 experts in all 6 layers, 151,936
    vocabulary rows, untied head) + K/V pools 3.62 GB (4,608 blocks x 6
    layers behind a table of 48), both pools aliased to their outputs, the
    step's scratch — 0.62 GB of fp32 logits for 256 x 4 rows among it —
    inside what is left of the chip's 16 GiB; the attend at 8 query heads x
    4 rows a K/V head, the row write and the grouped product TPU custom
    calls, six of each a program; no K/V-pool-sized op and no layer's
    experts (0.6 GB) copied; the block's update named under ``unmask`` in
    the block step and in no chunk program."""
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    spec, param_bytes, programs = block_step_programs
    compiled = programs[program]
    assert param_bytes == 2 * 4_361_055_744
    assert spec.max_blocks_per_slot == 48 and spec.name == "full"
    assert spec.nbytes() == 6 * 4608 * 64 * 2048
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= spec.nbytes()
    assert param_bytes + spec.nbytes() + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes \
        < 15.75 * 2 ** 30, (mem.temp_size_in_bytes,
                            mem.output_size_in_bytes)
    text = compiled.as_text()
    for kernel in (_ATTEND_KERNEL[program.split(".")[0]], "_kv_write_kernel",
                   "_gswiglu_kernel"):
        calls = [line for line in text.splitlines()
                 if f"%{kernel}" in line.split(" = ")[0]
                 and " custom-call(" in line]
        assert len(calls) == 6 \
            and all("tpu_custom_call" in c for c in calls), kernel
    seen = ops_in_units_of(text, math.prod(spec.pool_shapes["k.full"][2:]))
    assert not [(op, n) for op, n in seen
                if op not in _POOL_OPS_ALLOWED], seen
    one_layers_experts = 128 * 768 * 2048
    assert not [(op, n) for op, n in ops_in_units_of(
        text, one_layers_experts) if op not in ("parameter", "bitcast",
                                                 "get-tuple-element")]
    for scope in ("/moe/router", "/moe/dispatch", "/moe/experts",
                  "/moe/combine", "/attn/attend_full", "/attn/qkv_proj",
                  "/attn/kv_write", "/attn/out_proj", "/lm_head"):
        assert scope in text, scope
    assert ("/unmask" in text) == (program == "decode_step")

"""The fused optimizer's plan (ops/fused_update.update_plan): large
tile-aligned leaves are updated where they lie, everything else shares
one packed buffer. Transform-level numerics run in both layouts in
tests/test_fused_update.py; here: the plan from shapes, the block
geometry, and the engine tier — ZeRO 1/2/3 on four host devices with
sharded per-leaf moments and no apply-time collectives, checkpoints
under layout tag 3, and the plan the engine reports at start.

The chip compiler's view (no assembly left in the step, aliased
buffers, one kernel program per geometry) is in tests/test_tpu_compile.py.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init
from deepspeed_tpu.ops import fused_update
from deepspeed_tpu.ops.fused_update import (FusedAdamState, _leaf_blocks,
                                            _update_leaf, plan_summary,
                                            update_plan)
from deepspeed_tpu.parallel import hlo_audit
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine


def _gpt2_shapes(name, dtype=jnp.bfloat16):
    cfg = GPT2_CONFIGS[name]
    tree = jax.eval_shape(lambda k: gpt2_init(k, cfg), jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype), tree)


def _in_place_names(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    plan = update_plan([leaf for _, leaf in flat])
    return sorted(jax.tree_util.keystr(flat[i][0]) for i in plan.inplace)


# ------------------------------------------------------------------ #
# The plan, from shapes only
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("name", ["gpt2-large", "gpt2-medium"])
def test_plan_gpt2_scanned_six_matrices_in_place(name):
    tree = _gpt2_shapes(name)
    names = _in_place_names(tree)
    assert names == ["['blocks']['fc_kernel']",
                     "['blocks']['fc_out_kernel']",
                     "['blocks']['proj_kernel']",
                     "['blocks']['qkv_kernel']", "['wpe']", "['wte']"]
    summary = plan_summary(tree)
    assert summary["leaves_in_place"] == 6
    assert summary["leaves_packed"] == \
        len(jax.tree_util.tree_leaves(tree)) - 6
    assert summary["share_in_place"] > 0.999
    # six distinct geometries and the one packed group
    assert summary["kernel_programs"] == 7


def test_plan_off_tile_width_lands_packed():
    """gpt2-xl (width 1600 = 12.5 x 128): every leaf whose LAST dim is
    1600 or 4800 is off the lane tiling and stays packed; only the
    [.., 1600, 6400] matrix has a bitcast 2-D view."""
    tree = _gpt2_shapes("gpt2-xl")
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    plan = update_plan([leaf for _, leaf in flat])
    for i in plan.inplace:
        assert flat[i][1].shape[-1] % 128 == 0
    assert len(plan.inplace) == 1
    assert flat[plan.inplace[0]][1].shape[-2:] == (1600, 6400)
    # synthetic: rows off the sublane tile, and a small aligned leaf
    leaves = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
              [(1600, 1600), (3, 1000, 1024), (1 << 10, 1 << 10),
               (64, 128), (1 << 20,)]]
    assert update_plan(leaves).inplace == (2,)


def test_plan_same_for_f32_and_bf16():
    """The moments' layout is a checkpoint format and the master-free
    engine creates its state from an f32 view of bf16 params: the plan
    may not depend on the storage dtype."""
    a = update_plan(jax.tree_util.tree_leaves(
        _gpt2_shapes("gpt2-medium", jnp.float32)))
    b = update_plan(jax.tree_util.tree_leaves(
        _gpt2_shapes("gpt2-medium", jnp.bfloat16)))
    assert a.inplace == b.inplace
    assert [idxs for _, idxs in a.packed] == [idxs for _, idxs in b.packed]
    # non-float leaves are in neither list
    plan = update_plan([jnp.zeros((1024, 1024), jnp.int32)])
    assert plan.inplace == () and plan.packed == ()


@pytest.mark.parametrize("name", ["gpt2-large", "gpt2-medium"])
def test_blocks_divide_every_gpt2_geometry(name):
    """On one device every admitted leaf is cut into whole blocks of
    whole (32, 128) tiles near the packed kernel's block budget."""
    leaves = jax.tree_util.tree_leaves(_gpt2_shapes(name))
    for i in update_plan(leaves).inplace:
        shape = leaves[i].shape
        cols = shape[-1]
        rows = int(np.prod(shape)) // cols
        rb, cb = _leaf_blocks(rows, cols)
        assert rows % rb == 0 and cols % cb == 0, (shape, rb, cb)
        assert rb % 32 == 0 and cb % 128 == 0
        assert 32 * 1024 <= rb * cb <= 192 * 1024, (shape, rb, cb)


@pytest.mark.parametrize("shape", [(100, 128), (2100, 256), (40, 8192),
                                   (7, 36, 200)])
def test_update_leaf_ragged_shard_matches_dense(shape):
    """A dp shard of an admitted leaf may be off the tiling (ragged last
    block, width under a vreg, one block wider than the array): the
    update still covers every element — compared with the same math in
    jnp."""
    r = np.random.default_rng(0)
    g, p, m = (jnp.asarray(r.standard_normal(shape).astype(np.float32))
               for _ in range(3))
    v = jnp.abs(m) * 0.1
    neg_lr, bc1, bc2, coeff = -1e-2, 0.1, 0.001, 0.5
    scal = jnp.asarray([[neg_lr, bc1, bc2, coeff, 1.0, 0.0, 0.0, 0.0]],
                       jnp.float32)
    seed = jnp.zeros((1, 2), jnp.int32)
    pn, mn, vn = _update_leaf(
        g, p, m, v, scal, seed, b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
        coupled=False, use_inv=False, use_coeff=True, one_pass=True,
        sr=False, cast=False, out_dtype=jnp.dtype(jnp.float32),
        cast_dtype=None)
    gg = g * jnp.float32(coeff)
    m_ref = (1 - 0.9) * gg + 0.9 * m
    v_ref = (1 - 0.999) * (gg * gg) + 0.999 * v
    u = (m_ref / jnp.float32(bc1)) / (
        jnp.sqrt(v_ref / jnp.float32(bc2)) + 1e-8) + 0.01 * p
    # ulp tolerance: the reference runs op by op, the kernel as one
    # program (bit parity with optax is tests/test_fused_update.py's).
    for got, want in ((mn, m_ref), (vn, v_ref),
                      (pn, p + u * jnp.float32(neg_lr))):
        assert got.shape == shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-6)


# ------------------------------------------------------------------ #
# Engine tier: ZeRO over four host devices, in-place leaves sharded
# ------------------------------------------------------------------ #
D_IN, D_H, N_STACK = 64, 128, 4


@pytest.fixture
def small_leaves_in_place(monkeypatch):
    """Lower the size threshold so the fixture model's matrices are
    updated in place (the tiling rules stay as they are)."""
    monkeypatch.setattr(fused_update, "_INPLACE_MIN_ELEMS", 1 << 12)


def _params(seed=0):
    r = np.random.default_rng(seed)
    return {
        "w_in": jnp.asarray(r.standard_normal((D_IN, D_H)) * 0.1,
                            jnp.float32),
        "b_in": jnp.zeros((D_H,), jnp.float32),
        "stack": jnp.asarray(
            r.standard_normal((N_STACK, 32, D_H)) * 0.1, jnp.float32),
        "w_out": jnp.asarray(r.standard_normal((D_H,)) * 0.1, jnp.float32),
    }


def _loss_fn(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w_in"] + params["b_in"])
    for l in range(N_STACK):
        h = jnp.tanh(h[:, :32] @ params["stack"][l]) + h
    return jnp.mean((h @ params["w_out"] - batch["y"]) ** 2)


def _batch(i, n=32):
    r = np.random.default_rng(100 + i)
    x = r.standard_normal((n, D_IN)).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(np.tanh(x.sum(1)))}


def _cfg(stage, fused=True, dp=4, **over):
    cfg = {
        "train_batch_size": 32,
        "train_micro_batch_size_per_gpu": 32 // dp,
        "gradient_accumulation_steps": 1,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-2, "fused": fused}},
        "zero_optimization": {"stage": stage},
        "steps_per_print": 10 ** 9,
    }
    cfg.update(over)
    return cfg


def _engine(cfg, dp=4, seed=0):
    return DeepSpeedEngine(
        model=_loss_fn, model_params=_params(seed), config=cfg,
        mesh=build_mesh(devices=jax.devices()[:dp]))


def _losses(eng, steps=4):
    return [float(jax.device_get(eng.train_batch(_batch(i))))
            for i in range(steps)]


@pytest.mark.usefixtures("small_leaves_in_place")
@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_dp4_moments_sharded_and_parity(stage):
    """Each device updates its shard of each in-place leaf: per-leaf
    moments born dp-sharded like the leaf's gradient, the trajectory
    that of the optax apply."""
    eng = _engine(_cfg(stage))
    st = eng.state.opt_state
    assert isinstance(st, FusedAdamState) and len(st.leaf_m) == 2
    for mom in st.leaf_m + st.leaf_v:
        assert "data" in str(mom.sharding.spec), mom.sharding
        assert mom.addressable_shards[0].data.size * 4 == mom.size
    assert {tuple(m.shape) for m in st.leaf_m} == \
        {(D_IN, D_H), (N_STACK, 32, D_H)}
    ref = _engine(_cfg(stage, fused=False))
    np.testing.assert_allclose(_losses(eng), _losses(ref),
                               rtol=2e-5, atol=1e-6)
    for k in ("w_in", "stack", "b_in"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(eng.state.params[k])),
            np.asarray(jax.device_get(ref.state.params[k])),
            rtol=2e-5, atol=1e-6)


@pytest.mark.usefixtures("small_leaves_in_place")
@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_dp4_no_apply_time_collectives(stage):
    """Inside the optimizer nothing crosses devices but the scalar norm
    reduction: no gather of moments, no reshard of a leaf on its way
    into or out of the kernel (tools/comm_audit.py's gate, here with
    in-place leaves present)."""
    eng = _engine(_cfg(stage))
    mb = eng._stack_micro_batches(_batch(0))
    mb = jax.device_put(mb, eng._batch_sharding(mb, leading_dims=2))
    audit = hlo_audit.audit_jit(eng._build_train_step(), eng.state, mb,
                                eng._base_rng)
    leaf_bytes = D_IN * D_H * 4 // 4          # one dp shard of w_in
    inside = [o for o in audit.ops if "optimizer" in o.op_name]
    assert all(o.kind == "all-reduce" and o.payload_bytes <= 64
               for o in inside), [(o.kind, o.payload_bytes, o.op_name)
                                  for o in inside]
    # and nowhere in the step a moment-sized exchange other than the
    # ZeRO schedule's own (grad reduce-scatter / all-reduce, param
    # all-gather)
    odd = [o for o in audit.ops if o.payload_bytes >= leaf_bytes and
           "fwd_bwd" not in o.op_name and
           o.kind not in ("all-reduce", "reduce-scatter", "all-gather")]
    assert not odd, [(o.kind, o.payload_bytes, o.op_name) for o in odd]


@pytest.mark.usefixtures("small_leaves_in_place")
def test_checkpoint_roundtrip_and_dp_resize(tmp_path):
    """Layout tag 3: per-leaf moments and the packed buffer survive a
    sharded save at dp=4 and a load at dp=2 (neither layout depends on
    dp), and training continues on the same trajectory."""
    eng = _engine(_cfg(2))
    _losses(eng, 3)
    eng.save_checkpoint(str(tmp_path), tag="t3")
    with open(os.path.join(str(tmp_path), "t3", "engine_meta.json")) as f:
        assert json.load(f)["fused_moment_layout"] == 3
    eng2 = _engine(_cfg(2, dp=2), dp=2, seed=1)
    eng2.load_checkpoint(str(tmp_path), tag="t3")
    a, b = eng.state.opt_state, eng2.state.opt_state
    for x, y in zip(a.leaf_m + a.leaf_v + a.m + a.v,
                    b.leaf_m + b.leaf_v + b.m + b.v):
        np.testing.assert_array_equal(np.asarray(jax.device_get(x)),
                                      np.asarray(jax.device_get(y)))
    l1 = float(jax.device_get(eng.train_batch(_batch(50))))
    l2 = float(jax.device_get(eng2.train_batch(_batch(50))))
    assert abs(l1 - l2) < 1e-5, (l1, l2)


@pytest.mark.usefixtures("small_leaves_in_place")
def test_engine_reports_plan_at_start(tmp_path):
    """One ``fused_update_plan`` event at engine start: how often the
    mechanism engages."""
    out = tmp_path / "tel"
    eng = _engine(_cfg(2, telemetry={"enabled": True,
                                     "output_path": str(out)}))
    events = [e for e in eng.telemetry.events
              if e.get("event") == "fused_update_plan"]
    assert len(events) == 1
    ev = events[0]
    assert ev["leaves_in_place"] == 2 and ev["leaves_packed"] == 2
    n_in = D_IN * D_H + N_STACK * 32 * D_H
    assert ev["bytes_in_place"] == n_in * 12
    assert ev["bytes_packed"] == 2 * D_H * 12
    assert ev["kernel_programs"] == 3
    assert 0.98 < ev["share_in_place"] < 1.0


def test_gpt2_large_plan_summary_counts():
    """What the engine's start line says for gpt2-large: six leaves and
    over 99.9% of the optimizer's bytes in place."""
    s = plan_summary(_gpt2_shapes("gpt2-large"))
    assert s["leaves_in_place"] == 6
    assert s["share_in_place"] > 0.999
    total = (s["bytes_in_place"] + s["bytes_packed"]) // 10
    assert 773e6 < total < 776e6          # bf16 + two f32 moments


@pytest.mark.usefixtures("small_leaves_in_place")
def test_zero3_layer_scan_leaves_follow_stage3_specs():
    """Stacked leaves the ZeRO-3 layer scan covers are dp-sharded on a
    NON-leading dim (the layer axis stays whole): their in-place moments
    take that spec, not the first-divisible-dim rule, so the apply needs
    no reshard — and the trajectory is the optax apply's."""
    from deepspeed_tpu.models.gpt2 import gpt2_loss_fn
    from deepspeed_tpu.runtime.zero.stage3 import Zero3Scan
    cfg = dataclasses.replace(
        GPT2_CONFIGS["gpt2-tiny"], num_layers=4, hidden_size=128,
        num_heads=4, dtype=jnp.float32, hidden_dropout=0.0,
        attn_dropout=0.0, fused_kernels=False)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(16, 33)).astype(np.int32)

    def build(fused):
        spec = Zero3Scan()
        return DeepSpeedEngine(
            model=gpt2_loss_fn(cfg, zero3=spec),
            model_params=gpt2_init(jax.random.PRNGKey(0), cfg),
            config={"train_batch_size": 16,
                    "gradient_accumulation_steps": 1,
                    "gradient_clipping": 1.0,
                    "optimizer": {"type": "Adam",
                                  "params": {"lr": 1e-3, "fused": fused}},
                    "zero_optimization": {"stage": 3, "prefetch_depth": 1},
                    "steps_per_print": 10 ** 9},
            mesh=build_mesh(devices=jax.devices()[:4]), zero3_scan=spec)

    eng = build(True)
    p_leaves, p_def = jax.tree_util.tree_flatten(eng.state.params)
    plan = update_plan(p_leaves)
    assert len(plan.inplace) >= 4
    scanned = 0
    for k, i in enumerate(plan.inplace):
        mom = eng.state.opt_state.leaf_m[k]
        assert mom.shape == p_leaves[i].shape
        assert mom.sharding.spec == p_leaves[i].sharding.spec
        if p_leaves[i].ndim == 3:
            assert mom.sharding.spec[0] is None       # layer axis whole
            assert "data" in str(mom.sharding.spec)
            scanned += 1
    assert scanned >= 4
    mb = eng._stack_micro_batches(tokens)
    mb = jax.device_put(mb, eng._batch_sharding(mb, leading_dims=2))
    audit = hlo_audit.audit_jit(eng._build_train_step(), eng.state, mb,
                                eng._base_rng)
    # The packed group's small scanned leaves (biases, LayerNorm) still
    # relayout into their V-interleaved rows, as before this plan
    # (ops/fused_update docstring); nothing the size of an in-place
    # leaf's shard moves, and outside that assembly only the norm's
    # scalar crosses devices.
    inside = [o for o in audit.ops if "optimizer" in o.op_name]
    smallest = min(p_leaves[i].size for i in plan.inplace) * 4 // 4
    for o in inside:
        if "flatten" in o.op_name:
            assert o.payload_bytes < smallest // 4, (o.kind, o.op_name)
        else:
            assert o.kind == "all-reduce" and o.payload_bytes <= 64, \
                (o.kind, o.payload_bytes, o.op_name)
    ref = build(False)
    la = [float(jax.device_get(eng.train_batch(tokens))) for _ in range(3)]
    lb = [float(jax.device_get(ref.train_batch(tokens))) for _ in range(3)]
    np.testing.assert_allclose(la, lb, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("stage", [2, 3])
def test_deleting_the_engine_frees_its_state(stage):
    """The optimizer looks the engine's stage-3 specs up late; it may not
    hold the engine in a reference cycle, or ``del engine`` leaves
    gigabytes of device state to the garbage collector's next pass (on
    the chip: the next engine's program found no room to load)."""
    import gc
    import weakref
    eng = _engine(_cfg(stage))
    eng.train_batch(_batch(0))
    ref = weakref.ref(eng)
    eng.telemetry.close()
    gc.collect()
    gc.disable()
    try:
        del eng
        assert ref() is None
    finally:
        gc.enable()

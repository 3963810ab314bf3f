"""Sharded checkpoint layout + elastic dp-resize-on-load.

Reference: engine.py:1472-1572 save layout (mp_rank_XX model files,
zero_pp_rank_D per-dp-rank optim shards), stage1.py:848-1106 elastic
re-partitioning on a changed dp world size.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.parallel.topology import build_mesh

from simple_model import simple_loss_fn, simple_model_params, random_batch


def _engine(dp, lr=1e-2, seed=0, stage=2, slices=1):
    if slices > 1:
        # slices x dp must cover all 8 virtual devices (slice is the
        # outermost mesh axis; dp is the per-slice remainder).
        mesh = build_mesh(slices=slices)
        assert int(mesh.shape["data"]) == dp
    else:
        mesh = build_mesh(devices=jax.devices()[:dp])
    cfg = {
        "train_batch_size": 8 * dp * slices,
        "train_micro_batch_size_per_gpu": 8,
        "gradient_accumulation_steps": 1,
        "zero_optimization": {"stage": stage},
        "optimizer": {"type": "Adam", "params": {"lr": lr}},
        "steps_per_print": 10 ** 9,
    }
    return DeepSpeedEngine(model=simple_loss_fn,
                           model_params=simple_model_params(
                               jax.random.PRNGKey(seed)),
                           config=cfg, mesh=mesh)


def test_save_writes_per_rank_shard_files(tmp_path):
    eng = _engine(dp=4)
    eng.train_batch(random_batch(32, seed=0))
    eng.save_checkpoint(str(tmp_path), tag="t")
    files = sorted(os.listdir(tmp_path / "t"))
    for d in range(4):
        assert f"zero_pp_rank_{d}_mp_rank_00_optim_states.msgpack" in files
    assert "mp_rank_00_model_states.msgpack" in files
    # shard files are ~1/dp of the total moment bytes: rank>0 files hold
    # only sharded leaves
    sizes = [os.path.getsize(tmp_path / "t" /
                             f"zero_pp_rank_{d}_mp_rank_00_optim_states.msgpack")
             for d in range(4)]
    assert sizes[1] < sizes[0]            # rank0 carries scalars+replicated
    assert sizes[1] == sizes[2] == sizes[3]


@pytest.mark.parametrize("dp_load", [2, 8])
def test_dp_resize_on_load(tmp_path, dp_load):
    """Save at dp=4, load at dp=2 and dp=8 — optimizer state re-partitions
    and the loss trajectory continues."""
    eng = _engine(dp=4, lr=5e-2)
    for i in range(5):
        eng.train_batch(random_batch(32, seed=i))
    eng.save_checkpoint(str(tmp_path), tag="r")
    # continue the original engine one step for a reference trajectory
    ref_loss_next = float(jax.device_get(
        eng.train_batch(random_batch(32, seed=100))))

    eng2 = _engine(dp=dp_load, lr=5e-2, seed=1)
    p, _ = eng2.load_checkpoint(str(tmp_path), tag="r")
    assert p is not None
    b = jax.device_get(eng2.state.params)
    # compare against the SAVED state: reload into a third engine at dp=4
    eng3 = _engine(dp=4, lr=5e-2, seed=2)
    eng3.load_checkpoint(str(tmp_path), tag="r")
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(eng3.state.params)),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)
    # optimizer moments identical post-load (full assembly equality)
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(eng3.state.opt_state)),
                    jax.tree_util.tree_leaves(jax.device_get(eng2.state.opt_state))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)
    # training continues at the new dp size with a comparable loss
    l2 = float(jax.device_get(eng2.train_batch(
        random_batch(8 * dp_load, seed=100))))
    assert np.isfinite(l2)
    assert abs(l2 - ref_loss_next) < 0.5, (l2, ref_loss_next)


@pytest.mark.parametrize("dp_load,stage_load", [(2, 3), (8, 3), (4, 2)])
def test_stage3_checkpoint_elastic(tmp_path, dp_load, stage_load):
    """Stage-3 checkpoints are elastic BOTH ways: save under dp=4 /
    stage 3 (params dp-sharded on device, full arrays in the files),
    load under dp=2 and dp=8 — and under stage 2 — with bit-identical
    params and moments. The save path assembles full leaves from the
    shards; _place_state re-partitions for whatever layout the loading
    engine declares (extends the dp-resize pattern above to the
    parameter tree itself)."""
    eng = _engine(dp=4, lr=5e-2, stage=3)
    for i in range(4):
        eng.train_batch(random_batch(32, seed=i))
    eng.save_checkpoint(str(tmp_path), tag="z3")

    eng2 = _engine(dp=dp_load, lr=5e-2, seed=1, stage=stage_load)
    p, _ = eng2.load_checkpoint(str(tmp_path), tag="z3")
    assert p is not None
    if stage_load == 3 and dp_load > 1:
        assert "data" in str(eng2.state.params["w1"].sharding.spec)
    for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(eng.state.params)),
            jax.tree_util.tree_leaves(jax.device_get(eng2.state.params))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(eng.state.opt_state)),
            jax.tree_util.tree_leaves(jax.device_get(eng2.state.opt_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # training continues at the new world size / stage
    l2 = float(jax.device_get(eng2.train_batch(
        random_batch(8 * dp_load, seed=100))))
    assert np.isfinite(l2)


@pytest.mark.parametrize("direction", ["slices2_to_flat8",
                                       "flat8_to_slices2"])
def test_slice_elastic_stage3_checkpoint(tmp_path, direction):
    """ISSUE 18: the `slice` axis is checkpoint-elastic under stage 3.
    Save from a slices=2 x dp=4 stage-3 engine and resume on a flat
    dp=8 mesh — and vice versa — with params AND moments bit-identical.
    The save path assembles full leaves from the in-slice shards (the
    across-slice copies are replicas, so assembly is layout-free);
    _place_state re-partitions for whatever factorization the loading
    engine declares."""
    if direction == "slices2_to_flat8":
        src = _engine(dp=4, lr=5e-2, stage=3, slices=2)
        dst = _engine(dp=8, lr=5e-2, seed=1, stage=3)
    else:
        src = _engine(dp=8, lr=5e-2, stage=3)
        dst = _engine(dp=4, lr=5e-2, seed=1, stage=3, slices=2)
    for i in range(3):
        src.train_batch(random_batch(64, seed=i))
    src.save_checkpoint(str(tmp_path), tag="z3s")

    p, _ = dst.load_checkpoint(str(tmp_path), tag="z3s")
    assert p is not None
    spec = str(dst.state.params["w1"].sharding.spec)
    assert "data" in spec and "slice" not in spec
    for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(src.state.params)),
            jax.tree_util.tree_leaves(jax.device_get(dst.state.params))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(
            jax.tree_util.tree_leaves(
                jax.device_get(src.state.opt_state)),
            jax.tree_util.tree_leaves(
                jax.device_get(dst.state.opt_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    l2 = float(jax.device_get(dst.train_batch(
        random_batch(64, seed=100))))
    assert np.isfinite(l2)


def test_stage2_checkpoint_loads_into_stage3(tmp_path):
    """The reverse migration: a stage-2 checkpoint restores into a
    stage-3 engine bit-exactly (params re-partition on load)."""
    eng = _engine(dp=4, lr=5e-2, stage=2)
    for i in range(3):
        eng.train_batch(random_batch(32, seed=i))
    eng.save_checkpoint(str(tmp_path), tag="s2")
    eng3 = _engine(dp=4, lr=5e-2, seed=2, stage=3)
    p, _ = eng3.load_checkpoint(str(tmp_path), tag="s2")
    assert p is not None
    assert "data" in str(eng3.state.params["w1"].sharding.spec)
    for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(eng.state.params)),
            jax.tree_util.tree_leaves(jax.device_get(eng3.state.params))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_legacy_single_file_checkpoint_still_loads(tmp_path):
    """Old-layout checkpoints (single optim blob, no shard meta) load."""
    eng = _engine(dp=2)
    eng.train_batch(random_batch(16, seed=0))
    # write old layout by hand
    import json
    from flax import serialization
    path = tmp_path / "old"
    os.makedirs(path, exist_ok=True)
    host = jax.device_get(eng.state)
    with open(path / "mp_rank_00_model_states.msgpack", "wb") as f:
        f.write(serialization.to_bytes(
            {"module": jax.tree_util.tree_map(np.asarray, host.params)}))
    with open(path / "zero_pp_rank_0_mp_rank_00_optim_states.msgpack", "wb") as f:
        f.write(serialization.to_bytes({
            "opt_state": jax.tree_util.tree_map(np.asarray, host.opt_state),
            "step": np.asarray(host.step),
            "loss_scale": np.asarray(host.loss_scale),
            "growth_count": np.asarray(host.growth_count),
            "hysteresis": np.asarray(host.hysteresis),
            "skipped": np.asarray(host.skipped_steps)}))
    with open(path / "engine_meta.json", "w") as f:
        # fused_moment_layout=3: the blob above snapshots the CURRENT
        # engine's moment layout — the legacy part under test is the
        # single-blob FILE layout, not the moment layout (a moment blob
        # of an older layout is refused; see
        # test_fused_update.test_older_layout_checkpoint_refused).
        json.dump({"global_steps": 1, "global_samples": 16,
                   "skipped_steps": 0, "dp_world_size": 2,
                   "fused_moment_layout": 3,
                   "client_state": {}}, f)
    eng2 = _engine(dp=2, seed=3)
    p, _ = eng2.load_checkpoint(str(tmp_path), tag="old")
    assert p is not None
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(eng.state.params)),
                    jax.tree_util.tree_leaves(jax.device_get(eng2.state.params))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def test_mp_sharded_model_files(tmp_path):
    """TP runs write one model file per mp rank, each holding slices."""
    from jax.sharding import PartitionSpec as P
    mesh = build_mesh(mp=2, devices=jax.devices()[:4])   # dp=2 x mp=2
    params = {"w": jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8),
              "b": jnp.zeros((8,), jnp.float32)}

    def loss_fn(p, batch, rng):
        x, y = batch
        h = x @ p["w"][:x.shape[-1], :]
        return jnp.mean((h.sum(-1) - y) ** 2)

    eng = DeepSpeedEngine(
        model=loss_fn, model_params=params,
        config={"train_batch_size": 16, "train_micro_batch_size_per_gpu": 8,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9},
        mesh=mesh, param_shardings={"w": P("model", None), "b": P(None)})
    eng.train_batch(random_batch(16, seed=0))
    eng.save_checkpoint(str(tmp_path), tag="mp")
    files = os.listdir(tmp_path / "mp")
    assert "mp_rank_00_model_states.msgpack" in files
    assert "mp_rank_01_model_states.msgpack" in files
    eng2 = DeepSpeedEngine(
        model=loss_fn, model_params=jax.tree_util.tree_map(jnp.zeros_like,
                                                           params),
        config={"train_batch_size": 16, "train_micro_batch_size_per_gpu": 8,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9},
        mesh=mesh, param_shardings={"w": P("model", None), "b": P(None)})
    p, _ = eng2.load_checkpoint(str(tmp_path), tag="mp")
    assert p is not None
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(eng.state.params)),
                    jax.tree_util.tree_leaves(jax.device_get(eng2.state.params))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def test_pipeline_per_layer_files(tmp_path):
    """PipelineModule checkpoints write layer_NN-model_states files (tied
    params once) and reload through a PipelineEngine."""
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    def make_layer(dim):
        def layer(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])
        layer.init = lambda key: {
            "w": jax.random.normal(key, (dim, dim)) * 0.3,
            "b": jnp.zeros((dim,))}
        return layer

    layers = [make_layer(8) for _ in range(3)]

    def loss_head(x, labels):
        return jnp.mean((x.sum(-1) - labels) ** 2)

    model = PipelineModule(layers, num_stages=1, loss_fn=loss_head,
                           partition_method="uniform")
    params = {f"layer_{i}": layers[i].init(jax.random.PRNGKey(i))
              for i in range(3)}
    mesh = build_mesh(devices=jax.devices()[:1])
    cfg = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 8,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 10 ** 9}
    eng = PipelineEngine(model=model, model_params=params, config=cfg,
                         mesh=mesh)
    eng.train_batch(random_batch(8, seed=0))
    eng.save_checkpoint(str(tmp_path), tag="pp")
    files = os.listdir(tmp_path / "pp")
    for i in range(3):
        assert f"layer_{i:02d}-model_states.msgpack" in files
    assert "mp_rank_00_model_states.msgpack" not in files

    eng2 = PipelineEngine(model=model,
                          model_params=jax.tree_util.tree_map(
                              jnp.zeros_like, params),
                          config=cfg, mesh=mesh)
    p, _ = eng2.load_checkpoint(str(tmp_path), tag="pp")
    assert p is not None
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(eng.state.params)),
                    jax.tree_util.tree_leaves(jax.device_get(eng2.state.params))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


@pytest.mark.slow
def test_resume_continues_training_trajectory(tmp_path):
    """Save mid-run, load into a FRESH engine, keep training: the resumed
    run must land exactly where the uninterrupted run does (step counter,
    rng stream, optimizer moments and loss-scale state all restored) —
    the reference's checkpoint tier asserts this continuity, not just
    file round-trips."""
    batches = [random_batch(n=16, seed=100 + i) for i in range(40)]

    eng_a = _engine(dp=2)
    for b in batches:
        la = eng_a.train_batch(b)

    eng_b1 = _engine(dp=2)
    for b in batches[:20]:
        eng_b1.train_batch(b)
    eng_b1.save_checkpoint(str(tmp_path), tag="mid")

    eng_b2 = _engine(dp=2, seed=7)      # different init: load must win
    eng_b2.load_checkpoint(str(tmp_path), tag="mid")
    assert int(jax.device_get(eng_b2.state.step)) == 20
    for b in batches[20:]:
        lb = eng_b2.train_batch(b)

    np.testing.assert_allclose(float(jax.device_get(la)),
                               float(jax.device_get(lb)), rtol=1e-6)
    for pa, pb in zip(jax.tree_util.tree_leaves(
                          jax.device_get(eng_a.state.params)),
                      jax.tree_util.tree_leaves(
                          jax.device_get(eng_b2.state.params))):
        np.testing.assert_allclose(pa, pb, rtol=1e-6, atol=1e-7)

"""Multi-slice scale-out: hierarchical ICI/DCN gradient sync.

The tier-1 gates of the multislice round:

- **Audited collective hierarchy** (the acceptance gate): on the
  slices=2 x dp=4 CPU mesh, grads reduce-scatter IN-SLICE (groups of
  dp, inside the gas scan), the inter-slice all-reduce moves only the
  1/dp-sharded residual (groups of `slices`, once per step, outside the
  scan), never a grad-sized flat collective spanning the slice axis —
  and the compiled wire matches the two-tier analytic model on both
  tiers to 5%.
- **Bit-parity of hierarchical vs flat sync from identical state**: a
  2-slice run on a slice-DUPLICATED batch is BIT-identical to the
  1-slice run — every cross-slice float op is either the identical
  in-slice collective or an exact power-of-two scaling (the psum of two
  bitwise-equal partials, the /replicas mean correction).
- **DCN compression**: the priced DCN bytes drop >= 8x while the ICI
  bytes are unchanged; the error-feedback buffers live in EngineState
  and update per taken step.

Emulation honesty: "slices" on this box are virtual mesh axes over
XLA's host devices — everything asserted here is STRUCTURAL (which
collectives, what groups, what payloads) or NUMERIC (bit-parity);
nothing here measures DCN.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.parallel import comm, hlo_audit
from deepspeed_tpu.parallel.multislice import (SliceTopology,
                                               classify_two_tier,
                                               dcn_comm_bytes,
                                               dcn_compression_ratio,
                                               two_tier_wire_summary)
from deepspeed_tpu.parallel.topology import (DP_AXIS, SLICE_AXIS,
                                             build_mesh)


# ------------------------------------------------------------------ #
# Fixture model (tests/simple_model.py shape, kept local)
# ------------------------------------------------------------------ #
def _params(seed=0, dim=8, hidden=16, classes=4):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w1": jax.random.normal(k1, (dim, hidden)) * 0.1,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, classes)) * 0.1,
            "b2": jnp.zeros((classes,))}


def _loss_fn(params, batch, rng):
    x, y = batch
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    onehot = jax.nn.one_hot(y, logits.shape[-1])
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def _batch(n=16, dim=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32) % classes
    return (x, y)


def _engine(overrides=None, gas=1, slices=2, batch=16, devices=None,
            fp16=False, **kw):
    cfg = {"train_batch_size": batch * gas,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "Adam",
                         "params": {"lr": 1e-2, "fused": False}},
           "zero_optimization": {"stage": 2},
           "steps_per_print": 10 ** 9}
    if slices > 1:
        cfg["mesh"] = {"slices": slices}
    if fp16:
        cfg["fp16"] = {"enabled": True, "loss_scale": 128.0}
    for k, v in (overrides or {}).items():
        if isinstance(v, dict) and isinstance(cfg.get(k), dict):
            cfg[k].update(v)
        else:
            cfg[k] = v
    mesh = build_mesh(devices=devices) if devices is not None else None
    engine, *_ = deepspeed_tpu.initialize(
        model=_loss_fn, model_params=_params(), config=cfg, mesh=mesh,
        **kw)
    return engine


def _audit(engine, gas=1, n=16):
    batch = _batch(n=n * gas)
    mb = engine._stack_micro_batches(batch)
    mb = jax.device_put(mb, engine._batch_sharding(mb, leading_dims=2))
    return hlo_audit.audit_jit(engine._build_train_step(), engine.state,
                               mb, engine._base_rng)


def _assert_dcn_hop_carries_the_shards(audit, model, slices):
    """The inter-slice hop as the program guarantees it: all-reduces
    over groups of ``slices`` under the ``grad_sync`` scope, outside the
    gas scan, carrying in TOTAL the 1/dp residual the wire model prices
    (``dcn_payload_bytes``: every leaf's shard once) — as one op a leaf
    or as fewer, merged by XLA's all-reduce combiner. Scalars the
    combiner folds in beside them (the loss's sum over slices follows
    the hop) are not gradient bytes."""
    dcn = [o for o in audit.of_kind("all-reduce")
           if o.group_size == slices and "grad_sync" in o.op_name]
    assert dcn
    assert all(not o.in_loop for o in dcn)
    scalars = sum(s == "f32[]" for o in dcn for s in o.out_shapes)
    assert sum(o.payload_bytes for o in dcn) - 4 * scalars == \
        model["dcn_payload_bytes"], [(o.out_shapes, o.op_name) for o in dcn]


# ------------------------------------------------------------------ #
# Mesh / topology
# ------------------------------------------------------------------ #
class TestSliceMesh:
    def test_slice_axis_outermost_and_contiguous(self):
        mesh = build_mesh(slices=2)
        assert mesh.axis_names[0] == SLICE_AXIS
        assert int(mesh.shape[SLICE_AXIS]) == 2
        assert int(mesh.shape[DP_AXIS]) == 4
        # Slice 0 holds the first contiguous half of the devices (they
        # really share an ICI domain; DCN is the boundary between
        # halves).
        devs = mesh.devices
        ids0 = sorted(d.id for d in devs[0].reshape(-1))
        ids1 = sorted(d.id for d in devs[1].reshape(-1))
        assert max(ids0) < min(ids1)

    def test_dp_inferred_within_slice(self):
        mesh = build_mesh(slices=4)
        assert int(mesh.shape[DP_AXIS]) == 2

    def test_slice_topology_from_mesh(self):
        topo = SliceTopology.from_mesh(build_mesh(slices=2))
        assert (topo.num_slices, topo.dp_per_slice, topo.replicas) == \
            (2, 4, 8)

    def test_default_mesh_single_slice(self, mesh8):
        assert int(mesh8.shape.get(SLICE_AXIS, 1)) == 1


class TestSliceEmulationIdentity:
    """DS_PROC_INDEX / DS_PROC_COUNT / DS_NUM_SLICES -> (slice_id,
    rank-in-slice) — the PR-10 multi-host machinery grown a slice tier."""

    def test_mapping_two_slice_world(self, monkeypatch):
        from deepspeed_tpu.monitor.hostinfo import slice_identity
        monkeypatch.setenv("DS_PROC_COUNT", "4")
        monkeypatch.setenv("DS_NUM_SLICES", "2")
        seen = {}
        for p in range(4):
            monkeypatch.setenv("DS_PROC_INDEX", str(p))
            seen[p] = slice_identity()
        assert seen == {0: (0, 0, 2), 1: (0, 1, 2),
                        2: (1, 0, 2), 3: (1, 1, 2)}

    def test_explicit_num_slices_overrides_env(self, monkeypatch):
        from deepspeed_tpu.monitor.hostinfo import slice_identity
        monkeypatch.setenv("DS_PROC_INDEX", "5")
        monkeypatch.setenv("DS_PROC_COUNT", "8")
        monkeypatch.setenv("DS_NUM_SLICES", "2")
        assert slice_identity(4) == (2, 1, 4)

    def test_single_slice_default(self, monkeypatch):
        from deepspeed_tpu.monitor.hostinfo import slice_identity
        monkeypatch.setenv("DS_PROC_INDEX", "3")
        monkeypatch.setenv("DS_PROC_COUNT", "4")
        monkeypatch.delenv("DS_NUM_SLICES", raising=False)
        assert slice_identity() == (0, 3, 1)

    def test_indivisible_world_raises(self, monkeypatch):
        from deepspeed_tpu.monitor.hostinfo import slice_identity
        monkeypatch.setenv("DS_PROC_INDEX", "0")
        monkeypatch.setenv("DS_PROC_COUNT", "3")
        with pytest.raises(ValueError, match="not divisible"):
            slice_identity(2)

    def test_writer_resolution_unchanged_by_slices(self, monkeypatch):
        """Slice membership does not change WHO writes: global rank 0
        writes the primary stream; other ranks write their own shard
        iff per_host — even when they lead their own slice."""
        from deepspeed_tpu.monitor.hostinfo import (resolve_writer,
                                                    shard_path,
                                                    slice_identity)
        monkeypatch.setenv("DS_PROC_COUNT", "4")
        monkeypatch.setenv("DS_NUM_SLICES", "2")
        # Process 2 is slice 1's rank 0 — still NOT the global writer.
        monkeypatch.setenv("DS_PROC_INDEX", "2")
        assert slice_identity()[:2] == (1, 0)
        writes, rank, world = resolve_writer()
        assert (writes, rank, world) == (False, 2, 4)
        writes, rank, _ = resolve_writer(per_host=True)
        assert writes and shard_path("runs/job.jsonl", rank) == \
            "runs/job.rank2.jsonl"
        monkeypatch.setenv("DS_PROC_INDEX", "0")
        assert resolve_writer()[0] is True

    def test_per_host_telemetry_shards_two_slice_world(self, tmp_path,
                                                       monkeypatch):
        """A slice-1 host (global rank 2 of the 2x2 emulated world)
        writes its own telemetry shard; the records land in
        job.rank2.jsonl with the full meta."""
        from deepspeed_tpu.monitor.telemetry import Telemetry
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        cfg = DeepSpeedConfig({
            "train_batch_size": 8,
            "telemetry": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "job", "report_steps": 2,
                          "per_host_shards": True}}).telemetry_config
        monkeypatch.setenv("DS_PROC_INDEX", "2")
        monkeypatch.setenv("DS_PROC_COUNT", "4")
        monkeypatch.setenv("DS_NUM_SLICES", "2")
        tl = Telemetry(cfg, meta={"slices": 2})
        for s in range(2):
            tl.record_step(s, {"loss": jnp.asarray(0.5)}, wall_ms=1.0)
            tl.maybe_drain(s)
        tl.close()
        shard = tmp_path / "job.rank2.jsonl"
        assert shard.exists()
        recs = [json.loads(l) for l in
                shard.read_text().splitlines() if l.strip()]
        kinds = {r.get("kind") for r in recs}
        assert "meta" in kinds and "step" in kinds
        meta = [r for r in recs if r.get("kind") == "meta"][0]
        assert meta["slices"] == 2 and meta["process_index"] == 2


class TestSliceParallelAliasDeprecation:
    """Satellite: the reference's `slice parallel` accessors alias MODEL
    (tensor-slicing) parallelism — with a real `slice` mesh axis in
    play they warn, delegate, and point at the model-parallel names."""

    def test_old_names_warn_and_delegate(self):
        from deepspeed_tpu.parallel.topology import (
            PipeModelDataParallelTopology, PipelineParallelGrid)
        grid = PipelineParallelGrid(
            PipeModelDataParallelTopology(num_pp=2, num_mp=2, num_dp=2),
            global_rank=3)
        for name, expect in [
                ("get_slice_parallel_rank", grid.get_model_parallel_rank()),
                ("get_slice_parallel_world_size",
                 grid.get_model_parallel_world_size()),
                ("get_slice_parallel_group",
                 grid.get_model_parallel_group())]:
            with pytest.warns(DeprecationWarning,
                              match="tensor-slicing"):
                assert getattr(grid, name)() == expect
        with pytest.warns(DeprecationWarning, match="tensor-slicing"):
            assert grid.slice_parallel_size == \
                grid.get_model_parallel_world_size()

    def test_model_parallel_names_do_not_warn(self, recwarn):
        from deepspeed_tpu.parallel.topology import (
            PipeModelDataParallelTopology, PipelineParallelGrid)
        grid = PipelineParallelGrid(
            PipeModelDataParallelTopology(num_pp=1, num_mp=2, num_dp=4))
        grid.get_model_parallel_rank()
        grid.get_model_parallel_world_size()
        grid.get_model_parallel_group()
        assert not [w for w in recwarn.list
                    if issubclass(w.category, DeprecationWarning)]


# ------------------------------------------------------------------ #
# The two-tier wire model
# ------------------------------------------------------------------ #
class TestTwoTierWireModel:
    def test_hierarchical_terms(self):
        params = _params()
        dp, slices = 4, 2
        m = hlo_audit.grad_sync_wire_model(params, dp, slices=slices)
        scat_el = sum(int(np.prod(l.shape)) for l in
                      jax.tree_util.tree_leaves(params))
        # Toy tree: every leaf's dim divides dp=4 -> all scatterable.
        assert m["scatterable_bytes"] == scat_el * 4
        assert m["ici_wire_bytes"] == m["reduce_scatter_wire_bytes"]
        dcn_payload = scat_el // dp * 4
        assert m["dcn_payload_bytes"] == dcn_payload
        assert m["dcn_wire_bytes"] == hlo_audit.ring_wire_bytes(
            "all-reduce", dcn_payload, slices)
        assert m["flat_dcn_link_bytes"] == m["scatterable_bytes"]
        # Hierarchy divides the DCN traffic by dp vs the flat joint sync.
        assert m["flat_dcn_link_bytes"] // m["dcn_payload_bytes"] == dp
        assert m["hierarchical_wire_bytes"] == \
            m["ici_wire_bytes"] + m["dcn_wire_bytes"]

    def test_compression_prices_8x_down_and_flagship_32x(self):
        params = _params()
        m = hlo_audit.grad_sync_wire_model(params, 4, slices=2,
                                           dcn_compression=True)
        assert m["dcn_compression"] is True
        assert m["dcn_wire_bytes"] >= 8 * m["dcn_wire_bytes_compressed"]
        assert m["hierarchical_wire_bytes"] == \
            m["ici_wire_bytes"] + m["dcn_wire_bytes_compressed"]
        # Flagship shard sizes approach the 1-bit format's ~32x.
        assert dcn_compression_ratio(1 << 20, 2) > 28.0
        assert dcn_comm_bytes(64, compressed=True, num_slices=2) == \
            (64 + 7) // 8 + 4 * 2

    def test_classify_two_tier_signature(self):
        class Op:
            def __init__(self, kind, payload, group):
                self.kind = kind
                self.payload_bytes = payload
                self.group_size = group
                self.wire_bytes = payload
        ops = [Op("reduce-scatter", 1024, 4), Op("all-reduce", 256, 2),
               Op("all-reduce", 1024, 8), Op("all-reduce", 4, 2)]
        tiers = classify_two_tier(ops, num_slices=2, dp=4)
        assert [o.group_size for o in tiers["ici"]] == [4]
        assert [o.group_size for o in tiers["dcn"]] == [2]
        assert [o.group_size for o in tiers["flat"]] == [8]
        with pytest.raises(ValueError, match="ambiguous"):
            classify_two_tier(ops, num_slices=4, dp=4)


# ------------------------------------------------------------------ #
# Engine: resolution, validation, audited hierarchy
# ------------------------------------------------------------------ #
class TestMultisliceEngine:
    def test_resolves_explicit_and_prices_two_tiers(self):
        e = _engine()
        assert (e.slice_size, e.dp_size, e.replica_size) == (2, 4, 8)
        assert e._grad_sync_mode == "explicit"
        assert e._wire_bytes_dcn > 0
        assert e._wire_bytes > e._wire_bytes_dcn
        assert e.telemetry.meta["slices"] == 2 \
            if e.telemetry.enabled else True
        m = e._wire_model
        assert m["dcn_wire_bytes"] == e._wire_bytes_dcn

    def test_wire_tiers_are_per_step(self):
        """Both tiers in the same per-STEP units: the in-slice scatter
        repeats per micro-step (x gas), the DCN hop runs once — mixing
        a per-micro ICI figure with a per-step DCN figure would
        misreport the binding tier."""
        e1 = _engine(gas=1)
        e2 = _engine(gas=2)
        m = e1._wire_model
        assert e1._wire_bytes - e1._wire_bytes_dcn == \
            m["ici_wire_bytes"]
        assert e2._wire_bytes - e2._wire_bytes_dcn == \
            2 * m["ici_wire_bytes"]
        assert e2._wire_bytes_dcn == e1._wire_bytes_dcn

    def test_stage1_raises(self):
        with pytest.raises(ValueError, match="stage >= 2"):
            _engine({"zero_optimization": {"stage": 1}})

    def test_declarative_pin_raises(self):
        with pytest.raises(ValueError, match="hierarchical"):
            _engine({"zero_optimization": {"stage": 2,
                                           "grad_sync": "declarative"}})

    def test_dcn_compression_needs_slices(self):
        with pytest.raises(ValueError, match="multi.?slice"):
            _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}},
                    slices=1)

    def test_dcn_compression_config_needs_stage2(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        with pytest.raises(ValueError, match="stage >= 2"):
            DeepSpeedConfig({"train_batch_size": 8,
                             "zero_optimization": {
                                 "stage": 1, "dcn_compression": True}})

    def test_audited_collective_hierarchy_gate(self):
        """THE acceptance gate: in-slice reduce-scatter inside the gas
        scan, the inter-slice all-reduce on 1/dp shards only (once,
        outside the scan), no grad-sized collective spanning the slice
        axis, and both tiers within 5% of the analytic model."""
        gas = 2
        e = _engine(gas=gas)
        dp, slices = e.dp_size, e.slice_size
        audit = _audit(e, gas=gas)
        model = hlo_audit.grad_sync_wire_model(
            jax.device_get(e.state.params), dp, slices=slices)

        rs = audit.of_kind("reduce-scatter")
        assert rs, "no reduce-scatter compiled"
        assert all(o.group_size == dp for o in rs)
        assert all(o.in_loop for o in rs), \
            "in-slice scatter must sit inside the gas scan"
        assert sum(o.payload_bytes for o in rs) == \
            model["scatterable_bytes"]

        # Inter-slice hop: groups of `slices`, shard payloads, outside
        # the scan (ONE DCN exchange per step, not per micro-step).
        _assert_dcn_hop_carries_the_shards(audit, model, slices)

        # Never a grad-sized flat collective over the joint axes.
        flat = [o for o in audit.ops
                if o.kind in ("all-reduce", "reduce-scatter")
                and o.payload_bytes >= model["scatterable_bytes"] // 8
                and o.group_size > dp]
        assert not flat, [(o.kind, o.payload_bytes, o.group_size)
                          for o in flat]

        # Two-tier wire vs the analytic model, 5% on both tiers.
        tiers = two_tier_wire_summary(audit.ops, slices, dp,
                                      min_payload_bytes=1)
        assert abs(sum(o.wire_bytes for o in rs)
                   - model["ici_wire_bytes"]) <= \
            0.05 * model["ici_wire_bytes"]
        assert abs(tiers["dcn"] - model["dcn_wire_bytes"]) <= \
            0.05 * max(1, model["dcn_wire_bytes"])
        assert tiers["flat"] == 0

    def test_lint_collective_placement_clean(self, tmp_path):
        """The multislice flagship's compiled paths audit clean — the
        shard-payload DCN hop is whitelisted, nothing else fires."""
        e = _engine(gas=2, overrides={"telemetry": {
            "enabled": True, "output_path": str(tmp_path),
            "job_name": "msl", "report_steps": 10 ** 9}})
        for i in range(2):
            e.train_batch(batch=_batch(n=32, seed=i))
        report = e.lint_audit()
        cp = [f for f in report.findings
              if f.lint == "collective_placement"]
        assert not cp, [f.fingerprint for f in cp]
        e.telemetry.close()

    def test_whitelisted_dcn_hop_not_flagged_when_slices_gt_dp(self):
        """slices > dp with a byte collision (a 1/dp shard the size of a
        smaller leaf's full tensor): the legal inter-slice hop has
        groups wider than dp and a payload in the scatterable set — it
        must ride the dcn_shard_bytes whitelist through BOTH the
        grad-allreduce and the grad-spans-dcn checks."""
        from deepspeed_tpu.analysis.findings import LintContext
        from deepspeed_tpu.analysis.passes import \
            collective_placement_pass
        from deepspeed_tpu.parallel.hlo_audit import (CollectiveOp,
                                                      CommAudit)

        def op(kind, payload, group, in_loop=False):
            return CollectiveOp(
                kind=kind, name="x", computation="", out_bytes=payload,
                in_bytes=payload, out_shapes=[f"f32[{payload // 4}]"],
                in_shapes=[], group_size=group, num_groups=1,
                source_target_pairs=None, op_name="", in_loop=in_loop)

        # dp=2, slices=4; leaf A full 1024 B (shard 512), leaf B full
        # 512 B — B's full size == A's shard size.
        legal = [op("reduce-scatter", 1024, 2, in_loop=True),
                 op("reduce-scatter", 512, 2, in_loop=True),
                 op("all-reduce", 512, 4),    # A's shard over slices
                 op("all-reduce", 256, 4)]    # B's shard over slices
        meta = {"grad_sync_path": True, "grad_sync_mode": "explicit",
                "gas": 2, "scatterable_leaf_bytes": [1024, 512],
                "slices": 4, "dp": 2, "dcn_shard_bytes": [512, 256]}
        ctx = LintContext(name="hier", jaxpr=None, donated_invars=(),
                          in_avals=(), hlo_text="",
                          audit=CommAudit(legal), meta=meta)
        assert collective_placement_pass(ctx) == []
        # A genuinely flat grad-sized collective (full payload, joint
        # group) still fires.
        flat_ctx = LintContext(
            name="flat", jaxpr=None, donated_invars=(), in_avals=(),
            hlo_text="",
            audit=CommAudit(legal + [op("reduce-scatter", 1024, 8,
                                        in_loop=True)]), meta=meta)
        keys = [f.key for f in collective_placement_pass(flat_ctx)]
        assert any(k.startswith("grad-spans-dcn") for k in keys), keys

    def test_moe_ep1_stats_reduce_over_slices(self):
        """An ep=1 MoE model on a multislice mesh: the per-rank expert
        stats must reduce over (slice, data) — routed counts sum to
        top_k x the GLOBAL token count, not one slice's share."""
        import dataclasses as dc
        from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_init,
                                               gpt2_loss_fn)
        from deepspeed_tpu.moe import MoEConfig
        moe = MoEConfig(num_experts=4, top_k=2, capacity_factor=10.0,
                        expert_parallel_size=1)
        cfg = dc.replace(GPT2_CONFIGS["gpt2-tiny"], vocab_size=64,
                         max_seq_length=17, hidden_dropout=0.0,
                         attn_dropout=0.0, dtype=jnp.float32,
                         fused_kernels=False, moe=moe)
        engine, *_ = deepspeed_tpu.initialize(
            model=gpt2_loss_fn(cfg),
            model_params=gpt2_init(jax.random.PRNGKey(0), cfg),
            config={"train_batch_size": 16,
                    "gradient_accumulation_steps": 1,
                    "zero_optimization": {"stage": 2},
                    "mesh": {"slices": 2},
                    "optimizer": {"type": "Adam",
                                  "params": {"lr": 1e-3,
                                             "fused": False}},
                    "moe": {"num_experts": 4, "top_k": 2,
                            "capacity_factor": 10.0,
                            "expert_parallel_size": 1},
                    "steps_per_print": 10 ** 9})
        assert engine.slice_size == 2 and \
            engine._grad_sync_mode == "explicit"
        tokens = np.random.default_rng(0).integers(
            0, 64, size=(16, 18)).astype(np.int32)
        mb = engine._stack_micro_batches(tokens)
        mb = jax.device_put(mb,
                            engine._batch_sharding(mb, leading_dims=2))
        engine.state, metrics = engine._build_train_step()(
            engine.state, mb, engine._base_rng)
        # 16 samples x 17 routed tokens x top_k=2, summed over BOTH
        # replica axes (cf=10 => nothing drops, every token routes).
        total = float(jnp.sum(metrics["moe_expert_tokens"]))
        assert total == 16 * 17 * 2, total

    def test_seeded_flat_joint_sync_caught(self, mesh8):
        """A grad-sized collective whose groups span the slice axis (the
        flat joint sync the hierarchy exists to avoid) is flagged by the
        collective_placement slice check."""
        from deepspeed_tpu.analysis.auditor import lint_jit
        mesh = build_mesh(slices=2)
        n = 512

        def per_rank(w, x):
            g = w * x.sum()
            # FLAT: one psum_scatter over the JOINT (slice, data) group
            # — grad-sized traffic across the DCN boundary.
            return lax.psum_scatter(g, (SLICE_AXIS, DP_AXIS),
                                    scatter_dimension=0, tiled=True)

        fn = comm.shard_map(
            per_rank, mesh=mesh,
            in_specs=(P(), P((SLICE_AXIS, DP_AXIS))),
            out_specs=P((SLICE_AXIS, DP_AXIS)), check_vma=False)
        w = jnp.ones((n,), jnp.float32)
        x = jnp.ones((8, 4), jnp.float32)
        meta = {"grad_sync_path": True, "grad_sync_mode": "explicit",
                "gas": 1, "scatterable_leaf_bytes": [n * 4],
                "slices": 2, "dp": 4,
                "dcn_shard_bytes": [n * 4 // 4]}
        with mesh:
            res = lint_jit(jax.jit(fn), w, x, name="seeded_flat",
                           meta=meta, passes=["collective_placement"])
        assert not res.errors, res.errors
        keys = [f.key for f in res.findings]
        assert any(k.startswith("grad-spans-dcn") for k in keys), keys


# ------------------------------------------------------------------ #
# Bit-parity: hierarchical vs flat single-slice sync
# ------------------------------------------------------------------ #
class TestHierarchicalBitParity:
    """A 2-slice engine fed a slice-duplicated batch against the
    1-slice engine on the base batch: the HIERARCHICAL SYNC adds no
    rounding at all — the in-slice collectives run over the same
    values, and every cross-slice op is an exact power-of-two operation
    (x + x, /2^k). ONE step from identical state is therefore
    BIT-identical (params, moments, loss). Multi-step trajectories
    agree to a few f32 ulp only: the two engines are distinct XLA
    programs (different meshes), and FMA/fusion association across
    programs is the documented PR-1/PR-3 cross-program limit — not a
    property of the sync."""

    def _run_pair(self, gas=1, fp16=False, steps=1):
        base_n = 8 * gas
        flat = _engine(slices=1, devices=jax.devices()[:4],
                       batch=8, gas=gas, fp16=fp16)
        hier = _engine(slices=2, batch=16, gas=gas, fp16=fp16)
        assert flat.dp_size == hier.dp_size == 4
        for step in range(steps):
            x, y = _batch(n=base_n, seed=step)
            lf = flat.train_batch(batch=(x, y))
            lh = hier.train_batch(
                batch=(np.concatenate([x, x]), np.concatenate([y, y])))
        return flat, hier, lf, lh

    @pytest.mark.parametrize("gas", [1, 2])
    def test_one_step_bitwise(self, gas):
        flat, hier, lf, lh = self._run_pair(gas=gas, steps=1)
        assert float(lf) == float(lh)
        pf = jax.device_get(flat.state.params)
        ph = jax.device_get(hier.state.params)
        for k in pf:
            assert np.array_equal(np.asarray(pf[k]), np.asarray(ph[k])), k
        # Moments too: the optimizer consumed bitwise-equal grads.
        of = jax.device_get(flat.state.opt_state)
        oh = jax.device_get(hier.state.opt_state)
        for a, b in zip(jax.tree_util.tree_leaves(of),
                        jax.tree_util.tree_leaves(oh)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_fp16_scaled_path_one_step_bitwise(self):
        flat, hier, lf, lh = self._run_pair(fp16=True, steps=1)
        assert float(lf) == float(lh)
        pf = jax.device_get(flat.state.params)
        ph = jax.device_get(hier.state.params)
        for k in pf:
            assert np.array_equal(np.asarray(pf[k]), np.asarray(ph[k])), k

    def test_trajectory_within_ulp(self):
        """Three steps: losses stay exactly equal on this backend and
        params within a few f32 ulp (the cross-program FMA limit — the
        sync itself contributes zero of this, per the one-step bitwise
        gate above)."""
        flat, hier, lf, lh = self._run_pair(steps=3)
        assert float(lf) == pytest.approx(float(lh), abs=1e-6)
        pf = jax.device_get(flat.state.params)
        ph = jax.device_get(hier.state.params)
        for k in pf:
            np.testing.assert_allclose(np.asarray(pf[k]),
                                       np.asarray(ph[k]), atol=2e-7,
                                       rtol=0)


# ------------------------------------------------------------------ #
# DCN compression: numerics + state
# ------------------------------------------------------------------ #
class TestDcnCompression:
    def test_error_feedback_state_lives_and_updates(self):
        e = _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}})
        assert e.state.dcn_error is not None
        err0 = jax.device_get(e.state.dcn_error)
        shapes = {k: v.shape for k, v in err0.items()}
        assert shapes["w1"] == (2, 8, 16)     # [slices, *leaf]
        e.train_batch(batch=_batch(16))
        err1 = jax.device_get(e.state.dcn_error)
        assert any(not np.array_equal(np.asarray(err0[k]),
                                      np.asarray(err1[k]))
                   for k in err0)
        # The two slices carry DIFFERENT residuals (genuinely
        # per-slice state, like onebit's worker_error).
        assert not np.array_equal(np.asarray(err1["w1"][0]),
                                  np.asarray(err1["w1"][1]))

    def test_error_feedback_in_unscaled_units_under_fp16(self):
        """fp16 + dynamic-capable scaling: the carried residual is
        denominated in TRUE gradient units, not the loss scale — the
        error magnitudes must sit at gradient scale (<< the 128x-scaled
        grads), or a scale change would mis-weight every subsequent
        compensation."""
        e = _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}},
                    fp16=True)
        for i in range(3):
            e.train_batch(batch=_batch(16, seed=i))
        err = jax.device_get(e.state.dcn_error)
        scale = float(jax.device_get(e.state.loss_scale))
        assert scale == 128.0
        # A scaled-units residual would carry ~scale-sized magnitudes;
        # true-units residuals for this toy sit well under 1.
        worst = max(float(np.abs(np.asarray(v)).max())
                    for v in err.values())
        assert 0 < worst < 1.0, worst

    def test_priced_dcn_drops_8x_ici_unchanged(self):
        dense = _engine()
        comp = _engine({"zero_optimization": {"stage": 2,
                                              "dcn_compression": True}})
        ici_d = dense._wire_bytes - dense._wire_bytes_dcn
        ici_c = comp._wire_bytes - comp._wire_bytes_dcn
        assert ici_d == ici_c
        assert dense._wire_bytes_dcn >= 8 * comp._wire_bytes_dcn

    @pytest.mark.slow
    def test_compressed_training_converges(self):
        """Error-feedback 1-bit DCN sync still trains the toy task: the
        loss drops markedly from its start (lossy sync, no bit-parity
        claim — the claim is the error feedback keeps it unbiased)."""
        e = _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}})
        first = last = None
        for i in range(40):
            loss = float(e.train_batch(batch=_batch(32, seed=i % 4)))
            first = loss if first is None else first
            last = loss
        assert last < 0.6 * first, (first, last)

    def test_forward_backward_trio_refuses(self):
        e = _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}})
        with pytest.raises(NotImplementedError, match="train_batch"):
            e.forward(_batch(16))

    def test_error_feedback_checkpoint_roundtrip(self, tmp_path):
        """ISSUE 15 / ROADMAP 6(c): the carried residuals persist in
        the optim shards (``dcnN`` keys) and restore bit-exactly — a
        resume no longer restarts the feedback at zero, and the
        post-resume step matches the uninterrupted run bitwise."""
        e = _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}})
        for i in range(3):
            e.train_batch(batch=_batch(16, seed=i))
        err0 = jax.device_get(e.state.dcn_error)
        assert any(np.any(np.asarray(v) != 0) for v in err0.values())
        e.save_checkpoint(str(tmp_path), tag="dcn")
        e2 = _engine({"zero_optimization": {"stage": 2,
                                            "dcn_compression": True}})
        p, _ = e2.load_checkpoint(str(tmp_path), tag="dcn")
        assert p is not None
        err1 = jax.device_get(e2.state.dcn_error)
        for k in err0:
            np.testing.assert_array_equal(np.asarray(err0[k]),
                                          np.asarray(err1[k]))
        la = e.train_batch(batch=_batch(16, seed=9))
        lb = e2.train_batch(batch=_batch(16, seed=9))
        assert float(jax.device_get(la)) == float(jax.device_get(lb))
        for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(e.state.params)),
                jax.tree_util.tree_leaves(jax.device_get(e2.state.params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dcn_buffers_skipped_when_compression_off(self, tmp_path):
        """Skip-fetch both ways: an uncompressed engine neither saves
        dcn keys nor chokes loading a checkpoint that carries them."""
        e = _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}})
        e.train_batch(batch=_batch(16, seed=0))
        e.save_checkpoint(str(tmp_path), tag="dcn")
        plain = _engine()
        assert plain.state.dcn_error is None
        p, _ = plain.load_checkpoint(str(tmp_path), tag="dcn")
        assert p is not None
        assert plain.state.dcn_error is None
        plain.save_checkpoint(str(tmp_path), tag="plain")
        import json as _json
        meta = _json.load(
            open(tmp_path / "plain" / "engine_meta.json"))
        assert "dcn_error_shard_axes" not in meta

    def test_pre_resilience_checkpoint_warns_and_zeroes(self, tmp_path):
        """Loading an old checkpoint (no dcn buffers) into a compressed
        engine keeps the documented one-step-bias behavior: feedback
        restarts at zero, loudly."""
        import logging
        plain = _engine()
        plain.train_batch(batch=_batch(16, seed=0))
        plain.save_checkpoint(str(tmp_path), tag="old")
        e = _engine({"zero_optimization": {"stage": 2,
                                           "dcn_compression": True}})
        # The repo logger sets propagate=False, so pytest's caplog never
        # sees it — attach a handler directly.
        records = []

        class H(logging.Handler):
            def emit(self, r):
                records.append(r.getMessage())

        lg = logging.getLogger("deepspeed_tpu")
        h = H()
        lg.addHandler(h)
        try:
            p, _ = e.load_checkpoint(str(tmp_path), tag="old")
        finally:
            lg.removeHandler(h)
        assert p is not None
        assert any("dcn_error" in m for m in records)
        for v in jax.device_get(e.state.dcn_error).values():
            assert not np.any(np.asarray(v))


# ------------------------------------------------------------------ #
# Cost model / gate plumbing
# ------------------------------------------------------------------ #
class TestTwoTierCostModel:
    def test_roofline_dcn_tier(self):
        from deepspeed_tpu.monitor.cost_model import BOUND_DCN, roofline
        from deepspeed_tpu.monitor.peaks import peaks_for_kind
        peaks = peaks_for_kind("v5e")
        # Tiny DCN bytes dominate because the DCN ceiling is ~32x below
        # ICI: a step can be DCN-bound while ICI idles.
        r = roofline(flops_per_device=1e6, hbm_bytes_per_device=1e3,
                     comm_bytes=1e6, peaks=peaks, dcn_bytes=1e6)
        assert r["bound"] == BOUND_DCN
        assert r["t_dcn_ms"] > r["t_comm_ms"]
        r0 = roofline(1e12, 1e9, 0.0, peaks)
        assert r0["t_dcn_ms"] == 0.0 and r0["bound"] != BOUND_DCN

    def test_peaks_two_tier_column(self):
        from deepspeed_tpu.monitor.peaks import (TPU_DCN_GBS,
                                                 peaks_for_kind)
        pk = peaks_for_kind("TPU v5e")
        assert pk.dcn_gbs == TPU_DCN_GBS["v5e"] and not pk.assumed
        assert pk.ici_gbs / pk.dcn_gbs > 10
        assert "dcn_gbs" in pk.as_dict()
        assert peaks_for_kind("cpu").assumed

    def test_bench_gate_dcn_shapes(self, tmp_path):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_gate", os.path.join(os.path.dirname(__file__), "..",
                                       "tools", "bench_gate.py"))
        bg = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bg)

        def write(name, dcn):
            p = tmp_path / name
            p.write_text(json.dumps(
                {"multislice": {"available": True,
                                "dcn_bytes_per_step": dcn}}))
            return str(p)

        old = write("old.json", 1000)
        assert bg.gate(old, write("ok.json", 1050), 0.1, 0.05) == 0
        assert bg.gate(old, write("bad.json", 1200), 0.1, 0.05) == 1
        # Pre-multislice rounds skip, never fail.
        pre = tmp_path / "pre.json"
        pre.write_text(json.dumps({"mfu": 0.5}))
        assert bg.gate(str(pre), write("new.json", 900), 0.1, 0.05) == 0
        m = bg.extract_metrics(
            {"roofline": {"comm_tiers": {"wire_bytes_dcn": 77}}})
        assert m["dcn_bytes"] == 77.0

    def test_bench_gate_zero3_shapes(self, tmp_path):
        """The stage-3-across-slices gate: DCN bytes rise beyond the
        relative ceiling fails; the param-bytes ceiling over a
        structural 0 is 0, so ANY param byte leaking onto DCN fails;
        pre-composition rounds (no zero3 record) skip, never fail."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_gate", os.path.join(os.path.dirname(__file__), "..",
                                       "tools", "bench_gate.py"))
        bg = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bg)

        def write(name, dcn, param):
            p = tmp_path / name
            p.write_text(json.dumps(
                {"zero3": {"available": True,
                           "dcn_bytes_per_step": dcn,
                           "dcn_param_bytes_per_step": param}}))
            return str(p)

        old = write("old.json", 1000, 0)
        assert bg.gate(old, write("ok.json", 1050, 0), 0.1, 0.05) == 0
        assert bg.gate(old, write("rise.json", 1200, 0), 0.1, 0.05) == 1
        # One param byte on the slow tier = regression (0 * 1.1 = 0).
        assert bg.gate(old, write("leak.json", 1000, 1), 0.1, 0.05) == 1
        pre = tmp_path / "pre.json"
        pre.write_text(json.dumps({"mfu": 0.5}))
        assert bg.gate(str(pre), write("new.json", 900, 0),
                       0.1, 0.05) == 0
        # The ZERO3_BENCH.json shape (overlap_fraction) still resolves
        # independently of the multislice zero3 record.
        m = bg.extract_metrics({"zero3": {"overlap_fraction": 0.5}})
        assert m["zero3_overlap"] == 0.5
        assert m["z3_dcn_bytes"] is None and m["z3_dcn_param"] is None

    def test_ablate_record_shape(self, tmp_path):
        import subprocess
        import sys
        out = tmp_path / "MSL.json"
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..",
                          "ablate_multislice.py"),
             "--record", "--model", "gpt2-tiny", "--dp", "8",
             "--out", str(out)],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        rec = json.loads(out.read_text())
        ms = rec["multislice"]
        assert ms["available"] and ms["dcn_bytes_per_step"] > 0
        assert ms["flat_dcn_bytes_per_step"] > ms["dcn_bytes_per_step"]
        assert ms["dcn_reduction_compressed_vs_dense"] >= 8
        assert "PROJECTION" in rec["methodology"]
        scheds = rec["projection"]["schedules"]
        assert set(scheds) == {"flat", "hierarchical",
                               "hierarchical_1bit_dcn"}

    def test_ablate_zero3_record_shape(self, tmp_path):
        import subprocess
        import sys
        out = tmp_path / "MSL.json"
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..",
                          "ablate_multislice.py"),
             "--record", "--zero3", "--model", "gpt2-tiny", "--dp", "8",
             "--out", str(out)],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        rec = json.loads(out.read_text())
        z3 = rec["zero3"]
        assert z3["available"] and z3["dcn_param_bytes_per_step"] == 0
        assert z3["flat_dcn_link_bytes_per_step"] > \
            z3["dcn_bytes_per_step"]
        assert z3["ici_wire_bytes_per_step"] > 0
        assert "PROJECTION" in rec["methodology"]


# ------------------------------------------------------------------ #
# The axis-algebra planner (ISSUE 18 tentpole): one derivation for
# scope, schedule, tier, and group classification.
# ------------------------------------------------------------------ #
class TestAxisAlgebraPlanner:
    def test_factorization_from_mesh(self):
        from deepspeed_tpu.parallel.axis_algebra import MeshFactorization
        fact = MeshFactorization.from_mesh(build_mesh(slices=2))
        assert (fact.slices, fact.dp, fact.replicas) == (2, 4, 8)
        assert fact.tier(SLICE_AXIS) == "dcn"
        assert fact.tier(DP_AXIS) == "ici"
        assert fact.outer_axis == SLICE_AXIS
        assert fact.grad_shard_scope == (SLICE_AXIS, DP_AXIS)

    def test_plain_dp_mesh_has_no_outer(self):
        from deepspeed_tpu.parallel.axis_algebra import MeshFactorization
        fact = MeshFactorization.from_sizes(data=8)
        assert fact.outer_axis is None
        assert fact.grad_shard_scope == (DP_AXIS,)
        assert fact.replicas == 8

    def test_expert_outer_axis_rides_ici(self):
        """ep > 1 on a single slice: the residual hop binds `expert`,
        which is an in-slice axis — the planner derives the tier the
        MoE explicit path has always used."""
        from deepspeed_tpu.parallel.axis_algebra import (
            MeshFactorization, plan_grad_sync)
        fact = MeshFactorization.from_sizes(expert=2, data=4)
        assert fact.outer_axis == "expert"
        plan = plan_grad_sync(fact)
        assert plan.residual.tier == "ici"
        assert plan.residual.placement == "per-step"

    def test_unknown_axis_rejected(self):
        from deepspeed_tpu.parallel.axis_algebra import MeshFactorization
        with pytest.raises(ValueError, match="unknown mesh axis"):
            MeshFactorization.from_sizes(rows=2)

    def test_slice_x_expert_raises_with_structural_reason(self):
        from deepspeed_tpu.parallel.axis_algebra import MeshFactorization
        fact = MeshFactorization.from_sizes(slice=2, expert=2, data=2)
        with pytest.raises(ValueError,
                           match="one outer replica axis"):
            fact.outer_axis

    def test_classify_group_signatures(self):
        from deepspeed_tpu.parallel.axis_algebra import MeshFactorization
        fact = MeshFactorization.from_sizes(slice=2, data=4)
        assert fact.classify_group(4) == "ici"
        assert fact.classify_group(2) == "dcn"
        assert fact.classify_group(8) == "flat"
        assert fact.classify_group(3) == "other"
        amb = MeshFactorization.from_sizes(slice=4, data=4)
        with pytest.raises(ValueError, match="ambiguous"):
            amb.classify_group(4)

    def test_plan_zero3_multislice_headline(self):
        """THE derivation the PR is about: stage 3 on a (slice, data)
        mesh plans its param gathers on `data`/ICI in-scan and only the
        residual on `slice`/DCN — zero param bytes on the slow tier,
        by algebra rather than by special case."""
        from deepspeed_tpu.parallel.axis_algebra import (
            MeshFactorization, plan_grad_sync)
        fact = MeshFactorization.from_sizes(slice=2, data=4)
        plan = plan_grad_sync(fact, zero3=True)
        assert [s.op for s in plan.steps] == \
            ["all-gather", "reduce-scatter", "all-reduce"]
        assert plan.gather.axis == DP_AXIS
        assert plan.gather.tier == "ici"
        assert plan.gather.placement == "in-scan"
        assert plan.scatter.tier == "ici"
        assert plan.residual.axis == SLICE_AXIS
        assert plan.residual.tier == "dcn"
        assert plan.residual.placement == "per-step"
        # No zero3: no gather step, same residual.
        p2 = plan_grad_sync(fact)
        assert p2.gather is None and p2.residual.tier == "dcn"
        # Compression annotates only the DCN residual's wire format.
        p3 = plan_grad_sync(fact, zero3=True, dcn_compression=True)
        assert "1-bit" in p3.residual.payload
        assert "1-bit" not in p3.scatter.payload

    def test_plan_meta_roundtrips_to_json(self):
        from deepspeed_tpu.parallel.axis_algebra import (
            MeshFactorization, plan_grad_sync)
        plan = plan_grad_sync(MeshFactorization.from_sizes(slice=2,
                                                           data=4),
                              zero3=True)
        meta = json.loads(json.dumps(plan.to_meta()))
        assert [m["op"] for m in meta] == \
            ["all-gather", "reduce-scatter", "all-reduce"]
        assert all(set(m) == {"op", "axis", "tier", "placement",
                              "payload"} for m in meta)
        assert "all-gather[data/ici" in plan.describe()


# ------------------------------------------------------------------ #
# ZeRO-3 across slices (ISSUE 18 headline composition)
# ------------------------------------------------------------------ #
def _z3_engine(gas=1, slices=2, batch=16, devices=None, fp16=False,
               overrides=None):
    ov = {"zero_optimization": {"stage": 3}}
    for k, v in (overrides or {}).items():
        if isinstance(v, dict) and isinstance(ov.get(k), dict):
            ov[k].update(v)
        else:
            ov[k] = v
    return _engine(ov, gas=gas, slices=slices, batch=batch,
                   devices=devices, fp16=fp16)


class TestZero3Multislice:
    """Stage-3 params born dp-sharded WITHIN each slice and replicated
    across slices: every param all-gather binds `data` (ICI only), the
    grads reduce-scatter in-slice per micro-step, and the only DCN
    traffic is the accumulated 1/dp residual — once per step."""

    def test_resolves_and_prices_zero_param_bytes_on_dcn(self):
        e = _z3_engine()
        assert e._zero3 and (e.slice_size, e.dp_size) == (2, 4)
        assert e._grad_sync_mode == "explicit"
        m = e._wire_model
        assert m["dcn_param_bytes"] == 0
        assert m["param_gather_wire_bytes"] > 0
        # The ICI term carries scatter + both gathers; DCN carries the
        # residual only — same as stage 2 with the same tree.
        assert m["ici_wire_bytes"] == m["reduce_scatter_wire_bytes"] + \
            m["param_gather_wire_bytes"]
        s2 = _engine()
        assert m["dcn_wire_bytes"] == s2._wire_model["dcn_wire_bytes"]
        # The flat lowering would put both gathers on the DCN link too.
        assert m["flat_dcn_link_bytes"] == \
            s2._wire_model["flat_dcn_link_bytes"] + \
            2 * m["param_gather_payload_bytes"]
        plan = m["collective_plan"]
        assert [p["op"] for p in plan] == \
            ["all-gather", "reduce-scatter", "all-reduce"]
        assert plan[0]["tier"] == "ici" and plan[2]["tier"] == "dcn"

    def test_params_born_sharded_in_slice_replicated_across(self):
        e = _z3_engine()
        spec = e.state.params["w1"].sharding.spec
        assert DP_AXIS in str(spec) and SLICE_AXIS not in str(spec)

    def test_telemetry_meta_splits_wire_terms_by_tier(self, tmp_path):
        e = _z3_engine(overrides={"telemetry": {
            "enabled": True, "output_path": str(tmp_path),
            "job_name": "z3", "report_steps": 10 ** 9}})
        meta = e.telemetry.meta
        assert meta["wire_bytes_dcn"] == e._wire_bytes_dcn
        terms = meta["wire_terms"]
        assert terms["param_gather"]["tier"] == "ici"
        assert terms["grad_reduce_scatter"]["tier"] == "ici"
        assert terms["inter_slice_residual"]["tier"] == "dcn"
        assert terms["inter_slice_residual"]["bytes"] == \
            e._wire_bytes_dcn
        ici = sum(t["bytes"] for t in terms.values()
                  if t["tier"] == "ici")
        assert ici == e._wire_bytes - e._wire_bytes_dcn
        e.telemetry.close()

    def test_audited_zero3_collective_hierarchy_gate(self):
        """The stage-3 acceptance gate: in-slice gathers AND scatters
        inside the gas scan (groups of dp), ONE inter-slice all-reduce
        of residual size outside it, no param- or grad-sized collective
        spanning the slice axis, both tiers within 5% of the wire
        model (gather CSE tolerance: XLA may merge the fwd/bwd remat
        pair into one buffer — both counts accepted, priced as
        compiled)."""
        gas = 2
        e = _z3_engine(gas=gas)
        dp, slices = e.dp_size, e.slice_size
        audit = _audit(e, gas=gas)
        params = jax.device_get(e.state.params)
        model = hlo_audit.grad_sync_wire_model(
            params, dp, slices=slices, zero3=True, param_bytes_per_el=4,
            gas=1, param_specs=e._stage3_specs, mesh=e.mesh)

        ag = [o for o in audit.of_kind("all-gather")
              if o.payload_bytes >= 16]
        assert ag, "no param all-gather compiled"
        assert all(o.group_size == dp for o in ag), \
            [(o.payload_bytes, o.group_size) for o in ag]
        # Placement honesty: the DECLARED schedule re-gathers per
        # micro-step inside the gas scan; on this toy (params loop-
        # invariant across micro-steps) XLA hoists the gathers out via
        # LICM — once per step, strictly cheaper, still `data`-bound.
        # The in-scan claim is pinned where it is load-bearing: the
        # layer-scan program (params differ per layer, not hoistable —
        # tools/comm_audit.py zero3_multislice flagship).
        ag_payload = sum(o.payload_bytes for o in ag)
        ag_wire = sum(o.wire_bytes for o in ag)
        one_gather = hlo_audit.ring_wire_bytes(
            "all-gather", model["param_gather_payload_bytes"], dp)
        gathers = round(ag_payload /
                        max(1, model["param_gather_payload_bytes"]))
        assert gathers in (1, 2), (ag_payload,
                                   model["param_gather_payload_bytes"])
        assert abs(ag_wire - gathers * one_gather) <= 0.05 * ag_wire

        rs = audit.of_kind("reduce-scatter")
        assert rs and all(o.group_size == dp for o in rs)
        assert all(o.in_loop for o in rs)
        assert sum(o.payload_bytes for o in rs) == \
            model["scatterable_bytes"]
        assert abs(sum(o.wire_bytes for o in rs)
                   - model["reduce_scatter_wire_bytes"]) <= \
            0.05 * model["reduce_scatter_wire_bytes"]

        # ONE residual-sized DCN exchange per step, outside the scan.
        _assert_dcn_hop_carries_the_shards(audit, model, slices)
        tiers = two_tier_wire_summary(audit.ops, slices, dp,
                                      min_payload_bytes=1)
        assert abs(tiers["dcn"] - model["dcn_wire_bytes"]) <= \
            0.05 * max(1, model["dcn_wire_bytes"])
        assert tiers["flat"] == 0

        # Never a param- or grad-sized collective spanning `slice`.
        smallest_leaf = min(int(np.prod(l.shape)) * 4 for l in
                            jax.tree_util.tree_leaves(params))
        spanning = [o for o in audit.ops
                    if o.kind in ("all-gather", "all-reduce",
                                  "reduce-scatter")
                    and o.group_size > dp
                    and o.payload_bytes >= smallest_leaf]
        assert not spanning, [(o.kind, o.payload_bytes, o.group_size)
                              for o in spanning]

    def test_seeded_joint_axis_gather_caught(self, mesh8):
        """The seeded violation for the new lint check: a param-sized
        all-gather over the JOINT (slice, data) group ships param bytes
        across DCN every micro-step — collective_placement flags it as
        param-spans-dcn. The same gather bound to `data` alone audits
        clean."""
        from deepspeed_tpu.analysis.auditor import lint_jit
        mesh = build_mesh(slices=2)
        n = 512

        def flat_rank(w, x):
            full = lax.all_gather(w, (SLICE_AXIS, DP_AXIS), axis=0,
                                  tiled=True)
            return full * x.sum()

        def hier_rank(w, x):
            full = lax.all_gather(w, DP_AXIS, axis=0, tiled=True)
            return full * x.sum()

        w = jnp.ones((n,), jnp.float32)
        x = jnp.ones((8, 4), jnp.float32)
        # scatterable_leaf_bytes must be non-empty for the pass to run
        # at all (a grad-sync path with no scatterable leaves has no
        # gathers either); a size absent from the program keeps the
        # grad checks quiet.
        meta = {"grad_sync_path": True, "grad_sync_mode": "explicit",
                "gas": 1, "scatterable_leaf_bytes": [n * 16],
                "slices": 2, "dp": 4, "dcn_shard_bytes": [n * 4],
                "zero3_gather_leaf_bytes": [n * 4]}
        flat_fn = comm.shard_map(
            flat_rank, mesh=mesh,
            in_specs=(P((SLICE_AXIS, DP_AXIS)), P((SLICE_AXIS, DP_AXIS))),
            out_specs=P((SLICE_AXIS, DP_AXIS)), check_vma=False)
        with mesh:
            res = lint_jit(jax.jit(flat_fn), w, x, name="seeded_z3_flat",
                           meta=meta, passes=["collective_placement"])
        assert not res.errors, res.errors
        keys = [f.key for f in res.findings]
        assert any(k.startswith("param-spans-dcn") for k in keys), keys

        hier_fn = comm.shard_map(
            hier_rank, mesh=mesh,
            in_specs=(P((SLICE_AXIS, DP_AXIS)), P((SLICE_AXIS, DP_AXIS))),
            out_specs=P(DP_AXIS), check_vma=False)
        with mesh:
            ok = lint_jit(jax.jit(hier_fn), w, x, name="seeded_z3_hier",
                          meta=meta, passes=["collective_placement"])
        assert not ok.errors, ok.errors
        assert not [f for f in ok.findings
                    if f.key.startswith("param-spans-dcn")], \
            [f.key for f in ok.findings]

    def test_lint_collective_placement_clean(self, tmp_path):
        e = _z3_engine(gas=2, overrides={"telemetry": {
            "enabled": True, "output_path": str(tmp_path),
            "job_name": "z3l", "report_steps": 10 ** 9}})
        for i in range(2):
            e.train_batch(batch=_batch(n=32, seed=i))
        report = e.lint_audit()
        cp = [f for f in report.findings
              if f.lint == "collective_placement"]
        assert not cp, [f.fingerprint for f in cp]
        e.telemetry.close()

    def test_stage1_refusal_quotes_planner_reason(self):
        with pytest.raises(ValueError, match="no 1/dp residual"):
            _engine({"zero_optimization": {"stage": 1}})


class TestZero3MultisliceBitParity:
    """A 2-slice stage-3 engine on a slice-duplicated batch against the
    1-slice stage-3 engine on the base batch: the gathers run over the
    same in-slice values and every cross-slice float op is exact
    (x + x, /2^k) — ONE step is BIT-identical in params, moments, and
    loss, fp32 and fp16, gas 1 and 2."""

    def _run_pair(self, gas=1, fp16=False):
        flat = _z3_engine(slices=1, devices=jax.devices()[:4],
                          batch=8, gas=gas, fp16=fp16)
        hier = _z3_engine(slices=2, batch=16, gas=gas, fp16=fp16)
        assert flat.dp_size == hier.dp_size == 4
        x, y = _batch(n=8 * gas)
        lf = flat.train_batch(batch=(x, y))
        lh = hier.train_batch(
            batch=(np.concatenate([x, x]), np.concatenate([y, y])))
        return flat, hier, lf, lh

    @pytest.mark.parametrize("fp16", [False, True],
                             ids=["fp32", "fp16"])
    @pytest.mark.parametrize("gas", [1, 2])
    def test_one_step_bitwise(self, gas, fp16):
        flat, hier, lf, lh = self._run_pair(gas=gas, fp16=fp16)
        assert float(lf) == float(lh)
        pf = jax.device_get(flat.state.params)
        ph = jax.device_get(hier.state.params)
        for k in pf:
            assert np.array_equal(np.asarray(pf[k]), np.asarray(ph[k])), k
        of = jax.device_get(flat.state.opt_state)
        oh = jax.device_get(hier.state.opt_state)
        for a, b in zip(jax.tree_util.tree_leaves(of),
                        jax.tree_util.tree_leaves(oh)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_zero3_with_dcn_compression_trains(self):
        """zero3 x slices x dcn_compression: the composed engine builds,
        steps, and keeps its per-slice error feedback (lossy DCN wire —
        no bit-parity claim, same as stage 2)."""
        e = _z3_engine(overrides={"zero_optimization": {
            "stage": 3, "dcn_compression": True}})
        assert e.state.dcn_error is not None
        l0 = float(e.train_batch(batch=_batch(16, seed=0)))
        l1 = float(e.train_batch(batch=_batch(16, seed=1)))
        assert np.isfinite(l0) and np.isfinite(l1)
        err = jax.device_get(e.state.dcn_error)
        assert any(np.any(np.asarray(v) != 0) for v in err.values())

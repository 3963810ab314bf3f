"""PipelineModule tests — parity with reference tests/unit/test_pipe_module.py
(partitioning) plus tied-layer weight sharing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.pipe.module import (PipelineModule, LayerSpec,
                                               TiedLayerSpec)



class Dense:
    """Minimal flax-style layer for tests."""

    def __init__(self, din, dout):
        self.din, self.dout = din, dout

    def init(self, rng, x):
        return {"w": jax.random.normal(rng, (self.din, self.dout)) * 0.1}

    def apply(self, p, x, rngs=None):
        return jnp.tanh(x @ p["w"])

    def param_count(self):
        return self.din * self.dout


class TestPartitioning:
    def test_uniform(self):
        m = PipelineModule([LayerSpec(Dense, 4, 4) for _ in range(8)],
                           num_stages=4, partition_method="uniform")
        assert m.parts == [0, 2, 4, 6, 8]

    def test_parameters_balanced(self):
        # One huge layer + small ones: huge layer gets its own stage.
        specs = [LayerSpec(Dense, 64, 64)] + [LayerSpec(Dense, 4, 4)] * 7
        m = PipelineModule(specs, num_stages=2, partition_method="parameters")
        assert m.parts[1] == 1  # stage 0 holds only the big layer

    def test_type_regex(self):
        m = PipelineModule([LayerSpec(Dense, 4, 4) for _ in range(4)],
                           num_stages=2, partition_method="type:dense")
        assert m.parts[0] == 0 and m.parts[-1] == 4

    def test_stage_owner(self):
        m = PipelineModule([LayerSpec(Dense, 4, 4) for _ in range(8)],
                           num_stages=4, partition_method="uniform")
        assert m.stage_owner(0) == 0
        assert m.stage_owner(7) == 3

    def test_unknown_method(self):
        with pytest.raises(KeyError):
            PipelineModule([LayerSpec(Dense, 4, 4)], num_stages=1,
                           partition_method="bogus")


class TestTiedLayers:
    def test_tied_params_shared(self):
        def unembed_fwd(layer, p, x):
            return x @ p["w"].T

        specs = [
            TiedLayerSpec("embed", Dense, 4, 8),
            LayerSpec(Dense, 8, 8),
            TiedLayerSpec("embed", Dense, 4, 8, forward_fn=unembed_fwd),
        ]
        m = PipelineModule(specs, num_stages=1,
                           loss_fn=lambda logits, y: jnp.mean(logits ** 2))
        assert m.tied_specs == {"embed": [0, 2]}
        assert m.param_key(0) == m.param_key(2) == "tied_embed"
        assert m.param_key(1) == "layer_1"

    def test_tied_training_single_param_set(self):
        def unembed_fwd(layer, p, x):
            return x @ p["w"].T

        def loss_head(logits, y):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.sum(jax.nn.one_hot(y, 4) * logp, -1))

        specs = [
            TiedLayerSpec("embed", Dense, 4, 8),
            TiedLayerSpec("embed", Dense, 4, 8, forward_fn=unembed_fwd),
        ]
        from deepspeed_tpu.runtime.dataloader import ArrayDataset
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        ds = ArrayDataset(x, y)

        model = PipelineModule(specs, num_stages=1, loss_fn=loss_head)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config={"train_batch_size": 16,
                                 "optimizer": {"type": "Adam",
                                               "params": {"lr": 1e-2}}},
            training_data=ds)
        # exactly one param set for the tied pair
        assert set(jax.device_get(engine.state.params).keys()) == {"tied_embed"}
        losses = [float(engine.train_batch()) for _ in range(10)]
        assert losses[-1] < losses[0]


class TestPipelineEngineSingleStage:
    def test_trains(self):
        def loss_head(logits, y):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.sum(jax.nn.one_hot(y, 2) * logp, -1))

        specs = [LayerSpec(Dense, 8, 16), LayerSpec(Dense, 16, 2)]
        from deepspeed_tpu.runtime.dataloader import ArrayDataset
        rng = np.random.default_rng(1)
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int32)
        model = PipelineModule(specs, num_stages=2, loss_fn=loss_head)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config={"train_batch_size": 16,
                                 "optimizer": {"type": "Adam",
                                               "params": {"lr": 1e-2}}},
            training_data=ArrayDataset(x, y))
        losses = [float(engine.train_batch()) for _ in range(10)]
        assert losses[-1] < losses[0]


class TestToPipeSpec:
    def test_uniform_module_runs_pp2(self):
        """to_pipe_spec: a uniform PipelineModule trains on a pp=2 mesh via
        the compiled SPMD pipeline and matches the pp=1 fused trajectory."""
        import numpy as np
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        from deepspeed_tpu.parallel.topology import build_mesh

        def block(p, x):
            return x + jnp.tanh(x @ p["w"] + p["b"])

        L, D = 4, 8
        params = {
            f"layer_{i}": {
                "w": jax.random.normal(jax.random.PRNGKey(i), (D, D)) * 0.3,
                "b": jnp.zeros((D,))}
            for i in range(L)}

        def loss_head(x, labels):
            return jnp.mean((x.sum(-1) - labels) ** 2)

        module = PipelineModule([block] * L, num_stages=2,
                                loss_fn=loss_head,
                                partition_method="uniform")
        spec = module.to_pipe_spec(params)
        assert spec.num_layers == L

        cfg = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 2,
               "gradient_accumulation_steps": 2,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "steps_per_print": 10 ** 9}
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 4, D)).astype(np.float32)
        y = x.sum(axis=(-1, -2))

        mesh_pp = build_mesh(pp=2, devices=jax.devices()[:4])   # pp2 x dp2
        eng = PipelineEngine(model=spec, config=cfg, mesh=mesh_pp)
        losses = [float(jax.device_get(eng.train_batch((x, y))))
                  for _ in range(5)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

    def test_nonuniform_module_rejected(self):
        def block_a(p, x):
            return x + x @ p["w"]

        def block_b(p, x):
            return x - x @ p["w"]

        module = PipelineModule([block_a, block_b], num_stages=2,
                                loss_fn=lambda x, y: jnp.mean(x),
                                partition_method="uniform")
        params = {f"layer_{i}": {"w": jnp.eye(4)} for i in range(2)}
        with pytest.raises(ValueError, match="uniform stages"):
            module.to_pipe_spec(params)


class TestProfilePartitioning:
    """partition_method='profile': XLA cost-model-driven cuts. The
    reference never implemented this (module.py:374-375 raises); here a
    FLOPs-skewed model must get non-uniform cuts that beat uniform."""

    @staticmethod
    def _skewed_layers():
        def make(width, seed):
            a = jax.random.normal(jax.random.PRNGKey(seed), (64, width)) * .1
            b = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                  (width, 64)) * .1
            return lambda x: jnp.tanh(x @ a) @ b
        # Two heavy layers up front, six light ones behind.
        return [make(1024, 2 * i) for i in range(2)] + \
               [make(8, 100 + 2 * i) for i in range(6)]

    def test_requires_sample_input(self):
        with pytest.raises(ValueError):
            PipelineModule(self._skewed_layers(), num_stages=2,
                           partition_method="profile")

    def test_skewed_model_beats_uniform(self):
        layers = self._skewed_layers()
        x = jnp.ones((4, 64), jnp.float32)
        m = PipelineModule(layers, num_stages=2, partition_method="profile",
                           profile_input=x)
        mu = PipelineModule(layers, num_stages=2, partition_method="uniform")
        assert mu.parts == [0, 4, 8]
        # Profile must cut earlier than uniform: the two heavy layers
        # dominate, so stage 0 ends at or before layer 2.
        assert m.parts[1] <= 2, m.parts
        costs = m._profile_layer_costs(x)

        def stage_max(parts):
            return max(sum(costs[parts[s]:parts[s + 1]])
                       for s in range(len(parts) - 1))
        assert stage_max(m.parts) < stage_max(mu.parts)

    def test_profile_flax_layers(self):
        layers = [Dense(64, 64) for _ in range(4)]
        x = jnp.ones((4, 64), jnp.float32)
        m = PipelineModule(layers, num_stages=2, partition_method="profile",
                           profile_input=x)
        # Equal-cost layers: profile degrades to the uniform cut.
        assert m.parts == [0, 2, 4]

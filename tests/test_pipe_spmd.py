"""SPMD pipeline tests: pp>1 loss/grads must match the pp=1 computation.

The reference's equivalent is test_pipe.py's loss-parity runs of (pp, dp)
topologies against pure DP — here on the virtual 8-device CPU mesh.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import GPT2_CONFIGS
from deepspeed_tpu.models.gpt2 import gpt2_loss_fn
from deepspeed_tpu.models.gpt2_pipe import gpt2_pipe_spec
from deepspeed_tpu.parallel.topology import build_mesh



@pytest.fixture(scope="module")
def cfg():
    # dropout off so pp=1 vs pp=4 comparisons are exact-ish
    return dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], num_layers=4,
                               hidden_dropout=0.0, attn_dropout=0.0)


def _flat_params(spec):
    """PipeSpec params → models.gpt2 flat params layout."""
    return {**spec.params["shared"], "blocks": spec.params["blocks"]}


class TestSpmdPipeline:
    def test_pipeline_loss_matches_sequential(self, cfg):
        """pp=4 pipelined loss == plain gpt2 loss on identical params."""
        spec = gpt2_pipe_spec(cfg, rng=jax.random.PRNGKey(0))
        mesh = build_mesh(pp=4, dp=2)
        M = 4
        loss_fn = spec.loss_fn(num_stages=4, num_micro=M, mesh=mesh)
        batch = jax.random.randint(jax.random.PRNGKey(1), (M * 2, 17), 0,
                                   cfg.vocab_size)
        with jax.set_mesh(mesh):
            got = float(loss_fn(spec.params, batch, jax.random.PRNGKey(2)))
        want = float(gpt2_loss_fn(cfg)(_flat_params(spec), batch,
                                       jax.random.PRNGKey(2)))
        np.testing.assert_allclose(got, want, rtol=2e-2)

    @pytest.mark.slow
    def test_pipeline_grads_match_sequential(self, cfg):
        spec = gpt2_pipe_spec(cfg, rng=jax.random.PRNGKey(0))
        mesh = build_mesh(pp=4, dp=2)
        M = 4
        loss_fn = spec.loss_fn(num_stages=4, num_micro=M, mesh=mesh)
        batch = jax.random.randint(jax.random.PRNGKey(1), (M * 2, 17), 0,
                                   cfg.vocab_size)
        with jax.set_mesh(mesh):
            g_pipe = jax.jit(jax.grad(loss_fn))(spec.params, batch,
                                                jax.random.PRNGKey(2))
        g_seq = jax.grad(gpt2_loss_fn(cfg))(_flat_params(spec), batch,
                                            jax.random.PRNGKey(2))
        # blocks grads
        for k in g_seq["blocks"]:
            np.testing.assert_allclose(
                np.asarray(g_pipe["blocks"][k], np.float32),
                np.asarray(g_seq["blocks"][k], np.float32),
                rtol=5e-2, atol=5e-3, err_msg=f"blocks/{k}")
        # tied embedding grad: contributions from stage 0 (embed) AND last
        # stage (unembed) must both arrive (ReduceTiedGrads parity).
        np.testing.assert_allclose(
            np.asarray(g_pipe["shared"]["wte"], np.float32),
            np.asarray(g_seq["wte"], np.float32), rtol=5e-2, atol=5e-3)

    @pytest.mark.slow
    def test_engine_end_to_end_pp2_dp2_mp2(self, cfg):
        """Full 3D: PipelineEngine trains and the loss falls (pp2 dp2 mp2)."""
        spec = gpt2_pipe_spec(cfg, rng=jax.random.PRNGKey(0))
        mesh = build_mesh(pp=2, dp=2, mp=2)
        ds = {"train_batch_size": 16,            # micro 2 × dp 2 × gas 4
              "train_micro_batch_size_per_gpu": 2,
              "gradient_accumulation_steps": 4,
              "bf16": {"enabled": True},
              "zero_optimization": {"stage": 1},
              "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
              "steps_per_print": 1000}
        engine, *_ = deepspeed_tpu.initialize(config=ds, model=spec, mesh=mesh)
        batch = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(16, 17)).astype(np.int32)
        losses = [float(jax.device_get(engine.train_batch(batch)))
                  for _ in range(10)]
        assert losses[-1] < losses[0], losses

    def test_layer_divisibility_enforced(self, cfg):
        spec = gpt2_pipe_spec(dataclasses.replace(cfg, num_layers=3))
        mesh = build_mesh(pp=4, dp=2)
        ds = {"train_batch_size": 4, "train_micro_batch_size_per_gpu": 2,
              "gradient_accumulation_steps": 2,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
        with pytest.raises(ValueError):
            deepspeed_tpu.initialize(config=ds, model=spec, mesh=mesh)

"""CIFAR-10-scale training example (BASELINE.json configs[0]).

Mirrors DeepSpeedExamples/cifar: a small conv net driven entirely by the
ds_config JSON. Data is synthetic CIFAR-shaped (this environment has no
egress); swap ``synthetic_cifar`` for a real loader to train for real.

    python examples/cifar/train.py --steps 200 [--deepspeed_config ds_config.json]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.utils.compile_cache import enable_compile_cache


def net_apply(params, x):
    """3x conv (as grouped matmuls over patches) -> pooled linear head."""
    B = x.shape[0]
    h = x.reshape(B, 8, 4, 8, 4, 3).transpose(0, 1, 3, 2, 4, 5)
    h = h.reshape(B, 64, 48)                      # 4x4 patches
    h = jnp.tanh(h @ params["w1"] + params["b1"])
    h = jnp.tanh(h @ params["w2"] + params["b2"])
    h = h.mean(axis=1)                            # global average pool
    return h @ params["w3"] + params["b3"]


def init_params(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    s = 0.1
    return {
        "w1": jax.random.normal(k1, (48, 128)) * s, "b1": jnp.zeros((128,)),
        "w2": jax.random.normal(k2, (128, 128)) * s, "b2": jnp.zeros((128,)),
        "w3": jax.random.normal(k3, (128, 10)) * s, "b3": jnp.zeros((10,)),
    }


def loss_fn(params, batch, rng):
    x, y = batch
    logits = net_apply(params, x)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.sum(jax.nn.one_hot(y, 10) * logp, axis=-1))


def synthetic_cifar(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    # learnable labels: class = sign pattern of channel means
    y = ((x.mean(axis=(1, 2)) > 0) * np.array([1, 2, 4])).sum(-1) % 10
    return x, y.astype(np.int32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--deepspeed_config", default=None)
    ap.add_argument("--local_rank", type=int, default=0,
                    help="passed by the bin/deepspeed launcher")
    args = ap.parse_args()

    config = args.deepspeed_config or {
        "train_batch_size": 64,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 20}},
        "steps_per_print": 20,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=loss_fn, model_params=init_params(jax.random.PRNGKey(0)),
        config=config)
    x, y = synthetic_cifar(64 * 8)
    losses = []
    for step in range(args.steps):
        lo = (step * 64) % (64 * 8)
        loss = engine.train_batch((x[lo:lo + 64], y[lo:lo + 64]))
        losses.append(float(jax.device_get(loss)))
    # stdout contract consumed by tests/test_examples.py: the full curve
    # (decreasing-loss check) and the final value.
    print("losses:", " ".join(f"{l:.6f}" for l in losses))
    print(f"final loss: {losses[-1]:.4f}")


if __name__ == "__main__":
    main()

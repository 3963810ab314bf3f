"""BERT masked-LM pretraining example (BASELINE.json configs[1] shape:
BERT pretraining with ZeRO-1 + fused Adam). Synthetic MLM batches; plug a
real corpus by replacing ``synthetic_mlm``.

    python examples/bert/train.py --steps 50 [--model bert-tiny]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
from deepspeed_tpu.models.bert import (BERT_CONFIGS, bert_init,
                                       bert_mlm_loss_fn)


def synthetic_mlm(n, cfg, mask_prob=0.15, seed=0):
    rng = np.random.default_rng(seed)
    S = cfg.max_seq_length
    tokens = rng.integers(4, cfg.vocab_size, size=(n, S)).astype(np.int32)
    labels = np.full((n, S), -100, np.int32)
    mask = rng.random((n, S)) < mask_prob
    labels[mask] = tokens[mask]
    tokens = tokens.copy()
    tokens[mask] = 3          # [MASK]
    return tokens, labels


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--model", default="bert-tiny",
                    choices=sorted(BERT_CONFIGS))
    ap.add_argument("--local_rank", type=int, default=0,
                    help="passed by the bin/deepspeed launcher")
    args = ap.parse_args()

    cfg = BERT_CONFIGS[args.model]
    ds_config = {
        "train_batch_size": 8,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 10}},
        "steps_per_print": 10,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=bert_mlm_loss_fn(cfg),
        model_params=bert_init(jax.random.PRNGKey(0), cfg),
        config=ds_config)

    tokens, labels = synthetic_mlm(8 * 16, cfg)
    losses = []
    for step in range(args.steps):
        lo = (step * 8) % (len(tokens) - 8)
        loss = engine.train_batch((tokens[lo:lo + 8], labels[lo:lo + 8]))
        losses.append(float(jax.device_get(loss)))
    # stdout contract consumed by tests/test_examples.py: the full curve
    # (decreasing-loss check) and the final value.
    print("losses:", " ".join(f"{l:.6f}" for l in losses))
    print(f"final MLM loss: {losses[-1]:.4f}")


if __name__ == "__main__":
    main()

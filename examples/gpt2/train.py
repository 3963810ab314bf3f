"""GPT-2 training example — the Megatron_GPT2 config-matrix analogue.

Pick a ds_config from this directory (ZeRO-2, ZeRO-Offload, 1-bit Adam,
pipeline) or pass your own. Data defaults to synthetic token streams
(no egress); pass real data via --data: an .npy file of int32 [N, S+1]
token windows, or a .txt file (e.g. the vendored
examples/data/corpus.txt) which is byte-level tokenized into next-byte
prediction windows.

    python examples/gpt2/train.py --config ds_config_zero2.json --steps 50
    python examples/gpt2/train.py --config ds_config_offload.json
    python examples/gpt2/train.py --config ds_config_onebit.json
    python examples/gpt2/train.py --config ds_config_pipeline.json --pipeline
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init, gpt2_loss_fn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ds_config_zero2.json")
    ap.add_argument("--model", default="gpt2-tiny",
                    choices=sorted(GPT2_CONFIGS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--data", default=None,
                    help="npy int32 [N, S+1], or a .txt file "
                         "(byte-level tokenized)")
    ap.add_argument("--checkpoint_dir", default=None)
    ap.add_argument("--local_rank", type=int, default=0,
                    help="passed by the bin/deepspeed launcher")
    args = ap.parse_args()

    here = os.path.dirname(os.path.abspath(__file__))
    cfg_path = args.config if os.path.isabs(args.config) \
        else os.path.join(here, args.config)
    with open(cfg_path) as f:
        ds_config = json.load(f)

    cfg = GPT2_CONFIGS[args.model]
    if args.pipeline:
        from deepspeed_tpu.models.gpt2_pipe import gpt2_pipe_spec
        model = gpt2_pipe_spec(cfg, rng=jax.random.PRNGKey(0))
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=ds_config, model=model)
    else:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=gpt2_loss_fn(cfg),
            model_params=gpt2_init(jax.random.PRNGKey(0), cfg),
            config=ds_config)

    dev = jax.devices()[0]
    print(f"devices: {jax.device_count()} platform={dev.platform} "
          f"kind={dev.device_kind}")
    bs = ds_config["train_batch_size"]
    S = cfg.max_seq_length
    if args.data and args.data.endswith(".txt"):
        # Byte-level LM on real text: every UTF-8 byte is a token
        # (vocab 256 fits every config), windowed into [N, S+1] rows of
        # next-byte prediction. The reference for "the examples train
        # on REAL data" (they once trained on synthetic tokens only).
        raw = np.frombuffer(open(args.data, "rb").read(), dtype=np.uint8)
        n_rows = len(raw) // (S + 1)
        assert n_rows >= bs, f"corpus too small: {len(raw)} bytes"
        tokens = raw[:n_rows * (S + 1)].reshape(n_rows, S + 1) \
            .astype(np.int32)
        rng = np.random.default_rng(0)
        tokens = tokens[rng.permutation(n_rows)]
    elif args.data:
        tokens = np.load(args.data).astype(np.int32)
    else:
        # Markov synthetic stream: the next token is a fixed affine map of
        # the current one 90% of the time. A uniform random stream would
        # already sit AT the ln(V) optimum from init — unlearnable by
        # construction — while this has real next-token structure, so the
        # loss visibly decreases within a few dozen steps (the contract
        # tests/test_examples.py checks).
        rng = np.random.default_rng(0)
        n = bs * 16
        cols = [rng.integers(0, cfg.vocab_size, size=(n, 1))]
        resample = rng.random((n, S)) < 0.1
        rand = rng.integers(0, cfg.vocab_size, size=(n, S))
        for t in range(S):
            nxt = (cols[-1] * 7 + 1) % cfg.vocab_size
            cols.append(np.where(resample[:, t:t + 1],
                                 rand[:, t:t + 1], nxt))
        tokens = np.concatenate(cols, axis=1).astype(np.int32)

    assert len(tokens) >= bs, \
        f"need >= {bs} rows (train_batch_size), got {len(tokens)}"
    n_windows = max(1, len(tokens) - bs + 1)
    losses = []
    for step in range(args.steps):
        lo = (step * bs) % n_windows
        loss = engine.train_batch(tokens[lo:lo + bs])
        losses.append(float(jax.device_get(loss)))
    # stdout contract consumed by tests/test_examples.py: the full curve
    # (decreasing-loss check) and the final value.
    print("losses:", " ".join(f"{l:.6f}" for l in losses))
    print(f"final loss: {losses[-1]:.4f}")
    if args.checkpoint_dir:
        engine.save_checkpoint(args.checkpoint_dir)


if __name__ == "__main__":
    main()

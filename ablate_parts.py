"""Dev tool: differential component timing of the bench train step.

Measures full step, no-optimizer, fwd-only, attention-stubbed, and
headless variants (all with the chunked CE, so gpt2-large fits HBM) and
reports the deltas: optimizer, backward, attention, CE-head shares.
Usage: python ablate_parts.py [model] [mbs]
"""
import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deepspeed_tpu.models import GPT2_CONFIGS
from deepspeed_tpu.models.gpt2 import (gpt2_flops_per_token, gpt2_init,
                                       gpt2_loss_fn)

MODEL = sys.argv[1] if len(sys.argv) > 1 else "gpt2-large"
MBS = int(sys.argv[2]) if len(sys.argv) > 2 else 4

cfg = dataclasses.replace(GPT2_CONFIGS[MODEL], max_seq_length=1024,
                          remat_policy="dots", hidden_dropout=0.0,
                          attn_dropout=0.0, scan_layers=False)
S = cfg.max_seq_length
tx = optax.adamw(1e-4)


def attn_stub(q, k, v, **kw):
    # Stand-in with ~zero FLOPs but the right shape/dtype; keeps qkv+proj
    # matmuls so the delta vs base isolates the attention inner product.
    return v


def make_loss(attention_fn=None, headless=False):
    base = gpt2_loss_fn(cfg, attention_fn=attention_fn)
    if not headless:
        return base

    from deepspeed_tpu.models.gpt2 import gpt2_hidden

    def loss_fn(params, batch, rng):
        tokens = batch[:, :-1]
        x = gpt2_hidden(params, tokens, cfg, rng=rng, deterministic=False,
                        attention_fn=attention_fn)
        return jnp.mean(x.astype(jnp.float32) ** 2)
    return loss_fn


def cast(p):
    return jax.tree_util.tree_map(
        lambda a: a.astype(cfg.dtype) if a.dtype == jnp.float32 else a, p)


def sync(out):
    jax.block_until_ready(out)


def timeit(fn, args, n=20):
    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / n * 1000


def main():
    # NOTE: no optimizer state here — adamw state (2x fp32 params) plus the
    # non-donated step double-buffers would OOM gpt2-large on one chip.
    # Optimizer time = (full-step time from ablate_flash/bench) - fwd+bwd.
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    batch = jnp.asarray(np.random.randint(0, cfg.vocab_size,
                                          size=(MBS, S + 1), dtype=np.int32))
    rng = jax.random.PRNGKey(1)

    def gradonly(loss_fn):
        @jax.jit
        def step(params, batch, rng):
            return jax.value_and_grad(
                lambda p: loss_fn(cast(p), batch, rng))(params)
        return step

    def fwdonly(loss_fn):
        @jax.jit
        def step(params, batch, rng):
            return loss_fn(cast(params), batch, rng)
        return step

    base_loss = make_loss()
    stub_loss = make_loss(attention_fn=attn_stub)
    head_loss = make_loss(headless=True)

    t_grad = timeit(gradonly(base_loss), (params, batch, rng))
    t_fwd = timeit(fwdonly(base_loss), (params, batch, rng))
    t_grad_stub = timeit(gradonly(stub_loss), (params, batch, rng))
    t_grad_head = timeit(gradonly(head_loss), (params, batch, rng))

    tok = MBS * S
    fl = tok * gpt2_flops_per_token(cfg, S) / 1e12
    print(f"{MODEL} mbs={MBS} ({fl:.1f} TF/step)")
    print(f"  fwd+bwd          : {t_grad:7.1f} ms")
    print(f"  fwd only         : {t_fwd:7.1f} ms   -> backward  {t_grad-t_fwd:6.1f} ms")
    print(f"  fwd+bwd attn-stub: {t_grad_stub:7.1f} ms   -> attention {t_grad-t_grad_stub:6.1f} ms")
    print(f"  fwd+bwd headless : {t_grad_head:7.1f} ms   -> CE head   {t_grad-t_grad_head:6.1f} ms")


if __name__ == "__main__":
    main()

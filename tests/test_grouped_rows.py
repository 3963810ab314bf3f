"""The grouped gated product brings its rows in through the plan (PR 58):
``ops.grouped_gemm.grouped_swiglu`` takes the tokens ``x [T, H]`` and the
plan's ``src [M]`` and fills each row tile itself; ``moe/share._apply``'s
kernel arm no longer writes the padded ``[M, H]`` copy ``x[src]``.

A gather is exact, so the layer's output is held to the BYTES the parent's
kernel arm gave (``tests/data/grouped_rows_pr56.npz``: written by
``python tests/test_grouped_rows.py OUT.npz`` on PR 56's tree, kernel on in
interpret mode, every case under ``jax.jit``), one case a routing rule,
share, dtype, activation and tile.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.blocks import Routing
from deepspeed_tpu.moe import share

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "grouped_rows_pr56.npz")
H, F = 128, 256
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}

# name -> (rule, experts, per_tok, n_group, topk_group, held, dtype, act,
#          tokens, what else)
CASES = {
    "sigmoid_f32": ("sigmoid_bias", 8, 2, 1, 1, (0, 8), "f32", "silu", 24,
                    {}),
    "sigmoid_bf16": ("sigmoid_bias", 8, 2, 1, 1, (0, 8), "bf16", "silu", 24,
                     {}),
    "sigmoid_groups_bf16": ("sigmoid_bias", 16, 4, 4, 2, (0, 16), "bf16",
                            "silu", 20, {}),
    "softmax_relu_f32": ("softmax_topk", 8, 3, 1, 1, (0, 8), "f32", "relu",
                         20, {}),
    "softmax_relu_bf16": ("softmax_topk", 8, 3, 1, 1, (0, 8), "bf16", "relu",
                          20, {}),
    "softmax_silu_bf16": ("softmax_topk", 8, 3, 1, 1, (0, 8), "bf16", "silu",
                          20, {}),
    "held_share_f32": ("sigmoid_bias", 8, 2, 1, 1, (2, 4), "f32", "silu", 24,
                       {}),
    "held_share_bf16": ("softmax_topk", 16, 4, 1, 1, (5, 6), "bf16", "relu",
                        24, {}),
    "dead_rows_bf16": ("sigmoid_bias", 8, 2, 1, 1, (0, 8), "bf16", "silu", 24,
                       {"dead": True}),
    "dead_rows_f32": ("softmax_topk", 8, 3, 1, 1, (0, 8), "f32", "relu", 20,
                      {"dead": True}),
    "stacked_bf16": ("sigmoid_bias", 8, 2, 1, 1, (0, 8), "bf16", "silu", 24,
                     {"layers": (3, 1)}),
    "stacked_f32": ("softmax_topk", 8, 3, 1, 1, (0, 8), "f32", "relu", 20,
                    {"layers": (2, 1)}),
    "empty_expert_f32": ("sigmoid_bias", 8, 2, 1, 1, (0, 8), "f32", "silu", 24,
                         {"empty": 3}),
    "empty_expert_bf16": ("sigmoid_bias", 8, 2, 1, 1, (0, 8), "bf16", "relu",
                          24, {"empty": 0}),
    "wide_tile_bf16": ("softmax_topk", 4, 2, 1, 1, (0, 4), "bf16", "relu", 60,
                       {"tm": 64}),
    "wide_tile_f32": ("sigmoid_bias", 4, 2, 1, 1, (0, 4), "f32", "silu", 30,
                      {"tm": 32}),
    "one_row_bf16": ("softmax_topk", 8, 3, 1, 1, (0, 8), "bf16", "relu", 1,
                     {}),
    "all_at_once_bf16": ("softmax_topk", 16, 4, 1, 1, (3, 8), "bf16", "relu",
                         30, {"dead": True, "layers": (3, 2)}),
    "all_at_once_f32": ("sigmoid_bias", 16, 4, 4, 2, (4, 8), "f32", "silu",
                        30, {"dead": True, "layers": (2, 0), "empty": 6}),
}


def build(name):
    """(routing, params, x, row_live or None, layer or None, act) of a
    case: everything from a seed the case's place in ``CASES`` fixes."""
    rule, E, k, n_group, topk_group, held, dname, act, T, more = CASES[name]
    rng = np.random.default_rng(1000 + list(CASES).index(name))
    dtype = DTYPES[dname]
    r = Routing(E, k, n_group, topk_group, True, 2.5, held,
                norm_eps=1e-20 if rule == "sigmoid_bias" else 0.0, rule=rule)
    assert share._row_tile(T, r) == more.get("tm", 16), name
    stack = more.get("layers")
    lead = (held[1],) if stack is None else (stack[0], held[1])
    p = {key: jnp.asarray(rng.standard_normal(lead + (F, H)) * 0.1, dtype)
         for key in ("w_gate", "w_up", "w_down")}
    p["router"] = jnp.asarray(rng.standard_normal((H, E)) * 0.3, jnp.float32)
    if rule == "sigmoid_bias":
        bias = rng.standard_normal(E) * 0.1
        if "empty" in more:
            bias[more["empty"]] = -10.0             # never chosen
        p["router_bias"] = jnp.asarray(bias, jnp.float32)
    x = jnp.asarray(rng.standard_normal((T, H)), dtype)
    live = jnp.asarray(rng.random(T) > 0.3) if more.get("dead") else None
    layer = None if stack is None else jnp.asarray(stack[1], jnp.int32)
    return r, p, x, live, layer, act


def run(name):
    """The case's (y [T, H] as float32, counts) with the kernel on."""
    r, p, x, live, layer, act = build(name)
    y, counts = jax.jit(
        lambda p, x, live, layer: share.routed_share(
            p, x, r, kernel=True, layer=layer, row_live=live, act=act))(
                p, x, live, layer)
    return np.asarray(y.astype(jnp.float32)), np.asarray(counts)


@pytest.mark.parametrize("name", list(CASES))
def test_the_layer_is_the_parents_bit_for_bit(name):
    gold = np.load(GOLDEN)
    y, counts = run(name)
    assert np.array_equal(counts, gold[f"{name}.counts"])
    assert np.array_equal(y, gold[f"{name}.y"])
    assert np.isfinite(y).all() and np.abs(y).max() > 0.01
    more = CASES[name][-1]
    if "empty" in more:
        first = CASES[name][5][0]
        assert counts[more["empty"] - first] == 0
    if more.get("dead"):
        live = np.asarray(build(name)[3])
        assert not y[~live].any() and (~live).any()


@pytest.mark.parametrize("name", ["softmax_relu_f32", "held_share_f32",
                                  "all_at_once_f32"])
def test_the_kernel_arm_agrees_with_the_plain_arm(name):
    """The golden bytes are the kernel's; the ``jax.numpy`` arm (the layer's
    other path, which keeps its ``x[src]``) lands within float32 rounding."""
    r, p, x, live, layer, act = build(name)
    with jax.default_matmul_precision("highest"):
        want, c = share.routed_share(p, x, r, kernel=False, layer=layer,
                                     row_live=live, act=act)
    y, counts = run(name)
    assert np.array_equal(counts, np.asarray(c))
    np.testing.assert_allclose(y, np.asarray(want), atol=2e-5)


if __name__ == "__main__":
    out = {}
    for case in CASES:
        y, counts = run(case)
        assert np.isfinite(y).all(), case
        out[f"{case}.y"], out[f"{case}.counts"] = y, counts
        print(case, y.shape, counts.tolist(), float(np.abs(y).max()))
    np.savez_compressed(sys.argv[1], **out)

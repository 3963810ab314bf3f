"""The ``smallthinker`` family (SmallThinker-21BA3B-Instruct) through the
normal serving path (PR 54): every layer an expert layer whose router reads
the block's input BEFORE the attention, ReLU-gated experts chosen by their
largest logits (softmax over the chosen), 7 query heads a K/V head, a
position-free full layer and three rotary window layers a period, in TWO
CLASSES of one cache manager.

What is held to what, at a tiny size that KEEPS 7 query heads a K/V head,
the ``0 1 1 1`` layouts over 8 layers, top-3 of 8 experts and a window
shorter than the prompts:
1. Served logits — one chunk that covers the prompt, prefill chunks and
   decode through the two-class cache across returned blocks, a second
   request through the prefix-hit path, the scheduler's own loop — against
   the plain float32 reference the benchmark keeps
   (``perfbench/lib/smallthinker_reference.py``), kernels on (interpret
   mode) and off.
2. Every control the benchmark's ``correct`` relies on FAILS the same
   comparison: the window off, rotary on the full layers, the router
   reading the post-attention norm, SiLU for ReLU, a softmax over all the
   logits, 8-bit operands.
3. ``moe/share.py``: both rules of ``route`` against a NumPy transcription;
   two half shares of a ReGLU layer routed from ANOTHER tensor add up to
   the whole layer of the reference; ``_greglu_kernel`` (interpret mode)
   against plain ``jax.numpy``.
4. The default arguments leave the families that were served before where
   they were: ``tests/test_program_text.py`` holds every family's programs
   to the text they lowered to at PR 55 (this file's six ``decode_step``
   cases until PR 56, now cases of that test).
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import InferenceEngine             # noqa: E402
from deepspeed_tpu.inference import smallthinker as serving     # noqa: E402
from deepspeed_tpu.models import blocks                         # noqa: E402
from deepspeed_tpu.models.smallthinker import (                 # noqa: E402
    SmallthinkerConfig, smallthinker_init)
from deepspeed_tpu.moe import share                             # noqa: E402
from deepspeed_tpu.ops import grouped_gemm                      # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import smallthinker_reference as reference   # noqa: E402

ATOL = 5e-5


def one_device():
    return build_mesh(devices=jax.devices()[:1])


def tiny(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=14, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, sliding_window_size=8,
        max_position_embeddings=256, dtype=jnp.float32)
    base.update(kw)
    return SmallthinkerConfig(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["rope_layout"] = list(cfg.rope_layout)
    d["sliding_window_layout"] = list(cfg.sliding_window_layout)
    return d


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights moved off 1, so that a norm
    left out or applied on the wrong side shows."""
    params = smallthinker_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        a * jnp.asarray(rng.uniform(0.6, 1.4, a.shape), a.dtype)
        if "norm" in str(path[-1]) else a for path, a in leaves])


def engine_of(cfg, params, kernel, **inference):
    conf = dict(max_slots=4, max_seq_len=128, block_size=4, prefill_chunk=8,
                paged_kernel=kernel,
                num_blocks={"full": 64, "window": 40})
    conf.update(inference)
    return InferenceEngine(cfg, params, config={"inference": conf},
                           mesh=one_device())


def ref_logits(params, cfg, tokens, positions, **kw):
    lg, margin = reference.forward(
        params, jnp.asarray(np.asarray(tokens, np.int32)), sizes_of(cfg),
        out_positions=list(positions), q_block=16, **kw)
    return np.asarray(lg), np.asarray(margin)


# --------------------------------------------------------------------- #
# 0. The config
# --------------------------------------------------------------------- #
def test_the_layouts_and_the_classes_follow_the_published_lists():
    pub = SmallthinkerConfig()
    assert pub.rope_layout == pub.sliding_window_layout == (0, 1, 1, 1) * 13
    assert pub.group == 7 and pub.routing == blocks.Routing(
        experts=64, per_tok=6, n_group=1, topk_group=1, norm=True, scale=1.0,
        held=(0, 64), norm_eps=0.0, rule="softmax_topk")
    cfg = SmallthinkerConfig.from_hf({
        "num_hidden_layers": 8, "rope_layout": [0, 1, 1, 1] * 13,
        "sliding_window_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
        "model_name": "smallthinker_21b_instruct"})
    assert cfg.rope_layout == (0, 1, 1, 1, 0, 1, 1, 1)
    served = serving.SmallthinkerServed(cfg)
    assert [tuple(c) for c in served.cache_classes] == [
        ("full", 2, None, False), ("window", 6, 4096, False)]
    assert served.cache_pools(64) == (("k", (4, 64, 128)),
                                      ("v", (4, 64, 128)))
    with pytest.raises(ValueError):
        SmallthinkerConfig(num_hidden_layers=3, rope_layout=(0, 1))
    with pytest.raises(NotImplementedError):
        SmallthinkerConfig.from_hf({"rope_scaling": {"factor": 2.0}})
    with pytest.raises(NotImplementedError):
        SmallthinkerConfig(moe_primary_router_apply_softmax=False)
    # a window layer may carry no rotary and a full one may: two lists
    mixed = SmallthinkerConfig(num_hidden_layers=2, rope_layout=(1, 0),
                               sliding_window_layout=(0, 1))
    assert [c.name for c in
            serving.SmallthinkerServed(mixed).cache_classes] \
        == ["full", "window"]


def test_published_file_differs_from_the_source_in_depth_only():
    sizes = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "smallthinker-21b-a3b.json")))
    cfg = SmallthinkerConfig.from_hf(sizes)
    pub = SmallthinkerConfig()
    changed = {f.name for f in dataclasses.fields(cfg)
               if getattr(cfg, f.name) != getattr(pub, f.name)}
    assert changed == {"num_hidden_layers", "rope_layout",
                       "sliding_window_layout"}
    assert sizes["reduced"] == ["num_hidden_layers"]
    assert cfg.rope_layout == pub.rope_layout[:8]
    assert len(sizes["rope_layout"]) == 52               # kept whole
    assert len(sizes["sliding_window_layout"]) == 52
    shapes = jax.eval_shape(lambda k: smallthinker_init(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == sizes["assumed"]["parameters_held"] == 3_966_937_600
    inf = sizes["serve"]["inference"]
    assert inf["max_seq_len"] == cfg.max_position_embeddings
    assert inf["max_seq_len"] % inf["prefill_chunk"] == 0


def test_the_seeded_init_gives_unit_scale_router_logits_scores_and_logits():
    cfg = tiny(hidden_size=256, num_hidden_layers=1)
    p = smallthinker_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    lp = p["layers"][0]
    assert 0.8 < float(jnp.std(x @ lp["router"])) < 1.2
    assert 0.8 < float(jnp.std(x @ p["lm_head"].T)) < 1.2
    q = (x @ lp["wq"]).reshape(512, 14, 16)
    k = (x @ lp["wk"]).reshape(512, 2, 16)
    s = jnp.einsum("qhd,td->qht", q, k[:, 0]) * cfg.softmax_scale
    assert 0.8 < float(jnp.std(s)) < 1.2
    # ... so the six (here three) weights are far from equal
    _, w = share.route(x, lp["router"], None, cfg.routing)
    assert float(jnp.mean(w.max(-1) - w.min(-1))) > 0.15


# --------------------------------------------------------------------- #
# 1. Served logits against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernels"])
def test_one_chunk_is_the_models_forward(kernel):
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_of(cfg, params, kernel, prefill_chunk=64)
    assert list(eng.cache) == ["k.full", "v.full", "k.window", "v.window"]
    assert eng.cache["k.full"].shape == (2, 1, 64, 2, 1, 64)
    assert eng.cache["k.window"].shape == (6, 1, 40, 2, 1, 64)
    prompt = np.random.default_rng(0).integers(0, 128, 37, dtype=np.int32)
    slot = eng.select_slot(prompt, 2)
    _, got = eng.prefill(prompt, slot, return_logits=True, max_new_tokens=2)
    want, _ = ref_logits(params, cfg, prompt, [36])
    assert np.abs(got - want[0]).max() < ATOL
    eng.close()


def _decode_against_reference(eng, params, cfg, prompt, steps):
    """The prefill's and ``steps`` decode iterations' logits against ONE
    pass of the reference over prompt + emitted tokens (causal: a later
    token changes nothing before it)."""
    slot = eng.select_slot(prompt, steps + 1)
    tok, pre = eng.prefill(prompt, slot, return_logits=True,
                           max_new_tokens=steps + 1)
    info = dict(eng.last_admit_info(slot))
    info["returned_by_prefill"] = \
        eng.allocator.class_stats()["window"]["returned"]
    eng.activate_slot(slot, len(prompt), tok)
    toks, got = list(prompt) + [tok], [pre]
    for _ in range(steps):
        sampled, lg = eng.decode_once(return_logits=True)
        got.append(lg[slot])
        toks.append(int(sampled[slot]))
    want, _ = ref_logits(params, cfg, toks[:-1],
                         range(len(prompt) - 1, len(toks) - 1))
    return slot, info, float(np.abs(np.stack(got) - want).max())


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernels"])
def test_chunks_then_decode_across_returned_blocks_and_a_prefix_hit(kernel):
    """45 tokens = 5.6 windows of 8: the ring (5 blocks of 4) has turned
    over twice DURING the chunked prefill and keeps turning in decode."""
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_of(cfg, params, kernel)
    full, window = eng.allocator.classes
    assert (full.table_width, window.table_width) == (32, 5)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, 45, dtype=np.int32)
    slot, info, err = _decode_against_reference(eng, params, cfg, prompt, 12)
    assert err < ATOL
    assert info["cached_by_class"] == {"full": 0, "window": 0}
    # the last chunk's first query (40) reads from 33: blocks 0..7 went
    # back while the prompt was admitted
    assert info["returned_by_prefill"] == 8
    stats = eng.allocator.class_stats()
    assert stats["full"]["live"] == 15 and stats["full"]["returned"] == 0
    # positions 0..57 written; a query at 57 reads from 50: blocks 12..14
    assert stats["window"]["live"] == 3 and stats["window"]["returned"] == 12
    eng.release_slot(slot)
    assert eng.allocator.blocks_in_use() == 0
    # The second prompt shares 44 tokens = 11 blocks: the full class serves
    # all of them, the window class the two blocks a query at 44 reads.
    again = np.concatenate([prompt[:44], rng.integers(0, 128, 7,
                                                      dtype=np.int32)])
    slot, info, err = _decode_against_reference(eng, params, cfg, again, 6)
    assert err < ATOL
    assert info["cached_tokens"] == 44
    assert info["cached_by_class"] == {"full": 44, "window": 8}
    eng.release_slot(slot)
    eng.close()


def test_served_through_the_scheduler_with_the_counters(tmp_path):
    from deepspeed_tpu.inference.scheduler import Request
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_of(cfg, params, False)
    rng = np.random.default_rng(3)
    doc = rng.integers(0, 128, 40, dtype=np.int32)
    eng.serve([Request(rid=-1, prompt=doc, max_new_tokens=1, arrival_s=0.0)])
    eng.reset_serving_stats()
    reqs = [Request(rid=i, prompt=np.concatenate(
        [doc, rng.integers(0, 128, 5 + i, dtype=np.int32)]) if i % 2 else
        rng.integers(0, 128, 19 + i, dtype=np.int32),
        max_new_tokens=14, arrival_s=0.0) for i in range(6)]
    report = eng.serve(reqs)
    assert report["completed"] == 6 and report["recompiles"] == 0
    for r in reqs:
        toks = list(r.prompt) + list(r.out_tokens)
        want, _ = ref_logits(params, cfg, toks[:-1],
                             range(len(r.prompt) - 1, len(toks) - 1))
        assert list(np.argmax(want, -1)) == list(r.out_tokens)
    classes = report["cache_classes"]
    assert classes["window"]["returned"] > 0 == classes["full"]["returned"]
    assert report["model_counters"]["moe_held_pair_share"] == 1.0
    assert report["model_counters"]["rows"] > 0
    # what the admissions' chunks returned, and what each class had cached
    assert 0 < report["prefill_window_blocks_returned"] \
        <= classes["window"]["returned"]
    assert report["cached_tokens_full"] == 3 * 40
    assert report["cached_tokens_window"] == 3 * 8
    assert eng.allocator.blocks_in_use() == 0
    eng.close()


# --------------------------------------------------------------------- #
# 2. The controls fail where the served path passes
# --------------------------------------------------------------------- #
CONTROLS = {"window_off": dict(window=False),
            "rotary_on_full": dict(rotary_all=True),
            "router_reads_post_norm": dict(router_post=True),
            "silu_for_relu": dict(silu=True),
            "softmax_over_all": dict(softmax_all=True),
            "e4m3": dict(cast=jnp.float8_e4m3fn)}


@pytest.fixture(scope="module")
def served_and_true():
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_of(cfg, params, False)
    prompt = np.random.default_rng(5).integers(0, 128, 45, dtype=np.int32)
    slot = eng.select_slot(prompt, 2)
    _, got = eng.prefill(prompt, slot, return_logits=True, max_new_tokens=2)
    eng.close()
    return cfg, params, prompt, got, ref_logits(params, cfg, prompt, [44])[0]


def test_the_served_path_passes_the_comparison(served_and_true):
    _, _, _, got, want = served_and_true
    assert np.abs(got - want[0]).max() < ATOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_fails_the_comparison(served_and_true, control):
    cfg, params, prompt, got, _ = served_and_true
    wrong, _ = ref_logits(params, cfg, prompt, [44], **CONTROLS[control])
    assert np.abs(got - wrong[0]).max() > 100 * ATOL


def test_the_flags_name_every_control_and_the_true_model():
    assert set(reference.FLAGS) == set(reference.TRUE_MODEL)
    used = {k for kw in CONTROLS.values() for k in kw} - {"cast"}
    assert used == set(reference.FLAGS)


# --------------------------------------------------------------------- #
# 3. The expert layer under both rules
# --------------------------------------------------------------------- #
def _np_route(x, router, bias, r):
    logits = x.astype(np.float64) @ router.astype(np.float64)
    if r.rule == "softmax_topk":
        idx = np.argsort(-logits, axis=-1, kind="stable")[:, :r.per_tok]
        top = np.take_along_axis(logits, idx, -1)
        w = np.exp(top - top.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
    else:
        s = 1.0 / (1.0 + np.exp(-logits))
        idx = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :r.per_tok]
        w = np.take_along_axis(s, idx, -1)
    if r.norm:
        w = w / (w.sum(-1, keepdims=True) + r.norm_eps)
    return idx, w * r.scale


@pytest.mark.parametrize("rule", ["sigmoid_bias", "softmax_topk"])
def test_route_against_a_numpy_transcription(rule):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(33, 48)).astype(np.float32)
    router = (rng.normal(size=(48, 16)) * 48 ** -0.5).astype(np.float32)
    bias = (rng.normal(size=16) * 0.1).astype(np.float32)
    r = blocks.Routing(experts=16, per_tok=5, n_group=1, topk_group=1,
                       norm=True, scale=1.7, held=(0, 16), rule=rule)
    idx, w = share.route(jnp.asarray(x), jnp.asarray(router),
                         jnp.asarray(bias), r)
    want_idx, want_w = _np_route(x, router, bias, r)
    assert np.array_equal(np.asarray(idx), want_idx)
    assert np.abs(np.asarray(w) - want_w).max() < 1e-6
    if rule == "softmax_topk":      # the bias plays no part, given or not
        idx2, w2 = share.route(jnp.asarray(x), jnp.asarray(router), None, r)
        assert np.array_equal(np.asarray(idx2), want_idx)
        assert np.array_equal(np.asarray(w2), np.asarray(w))


def test_an_unknown_rule_is_refused():
    r = blocks.Routing(experts=4, per_tok=2, n_group=1, topk_group=1,
                       norm=True, scale=1.0, held=(0, 4), rule="softmax")
    with pytest.raises(ValueError, match="no routing rule"):
        share.route(jnp.ones((2, 8)), jnp.ones((8, 4)), None, r)


def _reglu_layer(seed=0, T=21, H=64, F=32, E=8):
    cfg = tiny(hidden_size=H, moe_ffn_hidden_size=F, num_hidden_layers=1)
    p = smallthinker_init(jax.random.PRNGKey(seed), cfg)["layers"][0]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)   # the router's
    z = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)   # the experts'
    return cfg, p, x, z


def _reference_layer(cfg, p, x, z):
    """The uncut reference's expert layer: dense, every expert."""
    ids, w, _ = reference.route(x, p["router"], sizes_of(cfg))
    y = jnp.zeros_like(z)
    for e in range(cfg.moe_num_primary_experts):
        we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        g = jnp.maximum(z @ p["w_gate"][e].T, 0.0) * (z @ p["w_up"][e].T)
        y = y + we[:, None] * (g @ p["w_down"][e])
    return y


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_two_shares_routed_from_another_tensor_add_up_to_the_layer(kernel):
    cfg, p, x, z = _reglu_layer()
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(cfg, p, x, z)
        whole, counts = share.routed_share(
            p, z, cfg.routing, kernel=kernel, router_input=x, act="relu")
        assert int(counts.sum()) == 21 * 3
        assert np.abs(np.asarray(whole - want)).max() < 1e-5
        parts = []
        for first in (0, 4):
            half = dict(p, **{k: p[k][first:first + 4]
                              for k in ("w_gate", "w_up", "w_down")})
            y, c = share.routed_share(
                half, z, cfg.routing._replace(held=(first, 4)),
                kernel=kernel, router_input=x, act="relu")
            assert np.array_equal(np.asarray(c),
                                  np.asarray(counts[first:first + 4]))
            parts.append(y)
        assert np.abs(np.asarray(parts[0] + parts[1] - want)).max() < 1e-5
        # routed from the experts' own input it is another layer
        own, _ = share.routed_share(p, z, cfg.routing, kernel=kernel,
                                    act="relu")
        assert np.abs(np.asarray(own - want)).max() > 1e-2
        # ... and so it is with a SiLU gate
        silu, _ = share.routed_share(p, z, cfg.routing, kernel=kernel,
                                     router_input=x)
        assert np.abs(np.asarray(silu - want)).max() > 1e-2


def test_plan_then_apply_is_routed_share():
    cfg, p, x, z = _reglu_layer(seed=2)
    live = jnp.arange(21) % 5 != 0
    d = share.plan_routes(p, x, cfg.routing, live)
    a, ca = share.apply_routes(p, z, d, cfg.routing, kernel=False,
                               act="relu")
    b, cb = share.routed_share(p, z, cfg.routing, kernel=False,
                               row_live=live, router_input=x, act="relu")
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(ca), np.asarray(cb))
    assert int(ca.sum()) == int(live.sum()) * 3
    assert not np.asarray(a)[~np.asarray(live)].any()


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_the_gated_kernels_against_plain_jnp(act):
    rng = np.random.default_rng(1)
    tm, H, F, E, T = 16, 128, 256, 4, 30
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    src = jnp.asarray(rng.integers(0, T, 6 * tm), jnp.int32)
    xs = x[src]                                     # the plain form's copy
    w = {k: jnp.asarray(rng.normal(size=(E, F, H)) * 0.1, jnp.float32)
         for k in ("w_gate", "w_up", "w_down")}
    te = jnp.asarray([0, 0, 2, 3, 3, 3], jnp.int32)
    full = jnp.full((6,), tm, jnp.int32)            # every tile's rows held
    with jax.default_matmul_precision("highest"):
        got = grouped_gemm.grouped_swiglu(
            x, src, w["w_gate"], w["w_up"], w["w_down"], te, full, 5, tm=tm,
            act=act)
        want = share._experts_jnp(xs, w, te, 5, tm, act)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert not np.asarray(got)[5 * tm:].any()       # the dead tile
    other = share._experts_jnp(xs, w, te, 5, tm,
                               "relu" if act == "silu" else "silu")
    assert np.abs(np.asarray(got - other)).max() > 1e-2


def test_the_relu_product_has_a_kernel_name_of_its_own():
    xs = jnp.zeros((16, 128), jnp.float32)
    w = jnp.zeros((2, 128, 128), jnp.float32)
    te = jnp.zeros((1,), jnp.int32)
    src = jnp.arange(16, dtype=jnp.int32)
    text = {act: jax.jit(lambda a, act=act: grouped_gemm.grouped_swiglu(
        a, src, w, w, w, te, te + 16, 1, tm=16, act=act)).lower(xs).as_text(
            debug_info=True) for act in ("silu", "relu")}
    assert "_gswiglu_kernel" in text["silu"] \
        and "_greglu_kernel" not in text["silu"]
    assert "_greglu_kernel" in text["relu"] \
        and "_gswiglu_kernel" not in text["relu"]

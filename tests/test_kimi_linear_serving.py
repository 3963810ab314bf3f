"""The ``kimi_linear`` family (Kimi-Linear-48B-A3B-Instruct) through the
normal serving path (PR 52): three Kimi-Delta-Attention layers (a gated delta
rule over an fp32 state a stream) to one NoPE latent-attention layer (a row a
token), over expert layers that hold a share of their experts.

What is held to what:
1. What the model declares and the shared code answers: a latent class of
   the latent layers beside a per-stream class of the KDA layers, pools of
   two dtypes, the layer pattern from ``linear_attn_config`` (1-based, cut in
   depth), the registry, no speculation.
2. Served logits and state pages — prefill chunks and decode through both
   pools, kernels on and off — against the plain float32 reference the
   benchmark keeps (``perfbench/lib/kimi_linear_reference.py``: the
   recurrence token by token).
3. A stream resumed from a snapshot (latent blocks by reference + the state
   the chunk program froze) against one served straight through; with the
   snapshot reclaimed the same prompt falls back and the loss is counted.
4. The shares of an expert layer, the shared expert counted once, add up to
   the uncut reference's layer.
5. The latent sublayer as one class of several: ``DeepseekV3Config`` with
   ``q_lora_rank: null`` and ``mla_use_nope`` serves through ``LatentServed``
   unchanged and rotates nothing.
6. The controls the benchmark's ``correct`` relies on are far from the
   served path at the small size too.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import InferenceEngine             # noqa: E402
from deepspeed_tpu.inference import kimi_linear as serving      # noqa: E402
from deepspeed_tpu.inference.kv_cache import (                  # noqa: E402
    ClassAllocators, class_specs, init_paged_cache)
from deepspeed_tpu.inference.served import (                    # noqa: E402
    filter_tile, served_model)
from deepspeed_tpu.models.blocks import rms_norm                # noqa: E402
from deepspeed_tpu.models.kimi_linear import (                  # noqa: E402
    KDA, LATENT, KimiLinearConfig, kimi_linear_init)
from deepspeed_tpu.moe import share                             # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import kimi_linear_reference as reference    # noqa: E402

BS, WIDTH, N_OUT = 4, 64, 6
# fp32 program against the fp32 reference: products at HIGH in the chunks
LOGIT_ATOL, PAGE_RTOL = 2e-4, 2e-5


def tiny(**kw):
    """4 layers K K K M (layer 1 dense, three expert layers); 2 KDA heads of
    16; 4 latent heads of 16 + 8 | 16 over a latent of 32; top-2 of 8
    experts, 4 held; 4 taps."""
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, kda_num_heads=2, kda_head_dim=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, held=(0, 4), num_experts_per_token=2,
        model_max_length=256, dtype=jnp.float32)
    base.update(kw)
    return KimiLinearConfig(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["rope_theta"] = 10000                  # (read by one control only)
    d["linear_attn_config"] = dict(
        kda_layers=list(cfg.kda_layers),
        full_attn_layers=list(cfg.full_attn_layers),
        num_heads=cfg.kda_num_heads, head_dim=cfg.kda_head_dim,
        short_conv_kernel_size=cfg.short_conv_kernel_size)
    return d


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights moved off 1, so that a norm
    left out or applied on the wrong side shows."""
    params = kimi_linear_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        a * jnp.asarray(rng.uniform(0.6, 1.4, a.shape), a.dtype)
        if "norm" in str(path[-1]) else a for path, a in leaves])


CFG = tiny()
_MADE = {}


def params():
    if "params" not in _MADE:
        _MADE["params"] = seeded(CFG)
    return _MADE["params"]


def engine(name):
    """The file's engines, built once: ``chunked`` (chunks of 8 rows, the
    kernels off), ``kernels`` (the same with the Pallas kernels in interpret
    mode), ``scarce`` (a state pool of two pages: a second stream's snapshot
    pushes the first out)."""
    if name not in _MADE:
        conf = dict(max_slots=4, max_seq_len=128, block_size=BS,
                    prefill_chunk=8, paged_kernel=name == "kernels",
                    num_blocks={"latent": 96, "state": 16})
        if name == "scarce":
            conf.update(max_slots=2, num_blocks={"latent": 96, "state": 2})
        _MADE[name] = InferenceEngine(
            CFG, params(), config={"inference": conf},
            mesh=build_mesh(devices=jax.devices()[:1]))
    return _MADE[name]


def ref(tokens, positions, state_at=0, zero_state_at=0, fault=None):
    """(logits, (state, filter rows) at ``state_at``) of the reference, one
    compiled function a variant for rows padded to WIDTH."""
    if ("ref", fault) not in _MADE:
        _MADE["ref", fault] = jax.jit(
            lambda p, t, out, at, cut: reference.forward(
                p, t, sizes_of(CFG), out_positions=out, q_block=16,
                state_at=at, zero_state_at=cut, fault=fault))
    row = np.zeros(WIDTH, np.int32)
    row[:len(tokens)] = tokens
    out = np.zeros(N_OUT, np.int32)
    out[:len(positions)] = positions
    lg, _, states = _MADE["ref", fault](
        params(), jnp.asarray(row), jnp.asarray(out), jnp.int32(state_at),
        jnp.int32(zero_state_at))
    return np.asarray(lg)[:len(positions)], \
        tuple(np.asarray(s) for s in states)


def page_of(eng, slot):
    """The stream's page, every KDA layer: (state [L, nh, dk, dv], filter
    rows [L, taps - 1, conv_dim])."""
    page = int(eng.block_tables[slot][-1])
    state = np.asarray(eng.cache["state.state"])[:, 0, page]
    conv = np.asarray(eng.cache["conv.state"])[:, 0, page]
    return state, conv.reshape(conv.shape[0],
                               CFG.short_conv_kernel_size - 1, CFG.conv_dim)


def through(eng, prompt, steps=2):
    """(tokens, logits of the prefill and of ``steps`` decode iterations,
    admission info, the page after prefill and after the last iteration) of
    ``prompt`` served alone."""
    slot = eng.select_slot(prompt, steps + 1)
    tok, pre = eng.prefill(prompt, slot, return_logits=True,
                           max_new_tokens=steps + 1)
    info = dict(eng.last_admit_info(slot))
    page0 = page_of(eng, slot)
    eng.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre)]
    for _ in range(steps):
        sampled, lg = eng.decode_once(return_logits=True)
        toks.append(int(sampled[slot]))
        got.append(np.asarray(lg[slot]))
    page1 = page_of(eng, slot)
    eng.release_slot(slot)
    return toks, np.stack(got), info, page0, page1


def prompt_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n,
                                                dtype=np.int32)


def rel(got, want):
    return float(np.sqrt(np.square(got - want).sum()
                         / max(np.square(want).sum(), 1e-30)))


def held(prompt, toks, got, page0, page1, steps=2, **variant):
    """(largest logit error, state error after prefill, after the last
    iteration, filter rows' error after the last iteration) of a served
    stream against the reference (a variant of it)."""
    n = len(prompt)
    seq = np.concatenate([prompt, toks[:-1]])
    at = [n - 1 + i for i in range(steps + 1)]
    want, (s0, _) = ref(seq, at, state_at=n - 1, **variant)
    _, (s1, c1) = ref(seq, at, state_at=at[-1], **variant)
    return (float(np.abs(got - want).max()), rel(page0[0], s0),
            rel(page1[0], s1), rel(page1[1], c1))


# --------------------------------------------------------------------- #
# 1. What the model declares and the shared code answers
# --------------------------------------------------------------------- #
def test_the_layer_pattern_is_read_from_the_lists_up_to_the_depth():
    cfg = KimiLinearConfig.from_hf(
        {"num_hidden_layers": 8, "linear_attn_config": {
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
            "full_attn_layers": [4, 8, 12], "num_heads": 32, "head_dim": 128,
            "short_conv_kernel_size": 4}, "num_experts": 256,
         "q_lora_rank": None, "rope_scaling": None}, held=(0, 16))
    assert cfg.layer_kinds == (KDA, KDA, KDA, LATENT) * 2
    assert (cfg.num_kda_layers, cfg.num_latent_layers) == (6, 2)
    assert (cfg.num_dense_layers, cfg.num_moe_layers) == (1, 7)
    assert cfg.conv_dim == 3 * 32 * 128 and cfg.latent_width == 576
    r = cfg.routing
    assert (r.experts, r.per_tok, r.n_group, r.topk_group, r.norm,
            r.scale, r.held) == (256, 8, 1, 1, True, 2.446, (0, 16))
    with pytest.raises(ValueError, match="name every layer"):
        KimiLinearConfig(num_hidden_layers=4, kda_layers=(1, 2),
                         full_attn_layers=(4,))
    with pytest.raises(NotImplementedError, match="full-rank query"):
        tiny(q_lora_rank=16)


def test_a_latent_class_stands_beside_a_per_stream_class_of_two_dtypes():
    served = served_model(tiny(dtype=jnp.bfloat16))
    assert isinstance(served, serving.KimiLinearServed)
    latent, state = served.cache_classes
    assert (latent.name, latent.layers, latent.per_stream) \
        == ("latent", 1, False)
    assert (state.name, state.layers, state.per_stream) == ("state", 3, True)
    specs = class_specs(
        served.cache_classes, {"latent": 24, "state": 6}, rows=8,
        of_class=lambda cls: served.class_geometry(cls, BS),
        num_slots=4, block_size=BS, max_len=128, num_groups=1,
        dtype=jnp.bfloat16)
    assert specs[0].pool_dtypes == {"latent.latent": jnp.bfloat16}
    assert specs[1].pool_dtypes == {"state.state": jnp.float32,
                                    "conv.state": jnp.bfloat16}
    assert specs[1].pool_shapes["state.state"] == (3, 1, 6, 2, 16, 16)
    # a latent block: one row of kv_lora + rope values a token and layer
    assert specs[0].block_nbytes() == 1 * BS * (32 + 8) * 2
    # a page's bytes count each pool in its own dtype
    assert specs[1].block_nbytes() == 3 * (2 * 16 * 16 * 4 + 3 * 96 * 2)
    # a KDA layer's share of what a token keeps as latent rows
    assert specs[1].token_row_bytes == -(-(40 * 1 * 2) // 3)
    assert specs[1].program_rows == 8 and specs[1].page_tokens == 8
    pools = {}
    for spec in specs:
        pools.update(init_paged_cache(spec))
    assert {n: p.dtype for n, p in pools.items()} == {
        "latent.latent": jnp.bfloat16, "state.state": jnp.float32,
        "conv.state": jnp.bfloat16}
    assert isinstance(engine("chunked").allocator, ClassAllocators)
    assert engine("chunked").served.table_widths == (32, 1)


def test_a_state_cannot_be_rolled_back_so_speculation_is_refused():
    with pytest.raises(ValueError, match="spec_k"):
        InferenceEngine(CFG, params(), config={"inference": dict(
            max_slots=2, max_seq_len=64, block_size=BS, prefill_chunk=8,
            spec_k=2)}, mesh=build_mesh(devices=jax.devices()[:1]))
    with pytest.raises(NotImplementedError, match="rolled back"):
        served_model(CFG).verify(None, None, None, None, None,
                                 num_groups=1, paged_kernel=False)


# --------------------------------------------------------------------- #
# 2. Through both pools against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["chunked", "kernels"])
@pytest.mark.parametrize("n", [21, 8, 5])
def test_prefill_then_decode_through_both_pools_is_the_reference(name, n):
    """21 tokens: three chunk programs, the state carried from one to the
    next; 8: one whole chunk; 5: a chunk with dead rows."""
    eng = engine(name)
    prompt = prompt_of(n, n)
    toks, got, info, page0, page1 = through(eng, prompt)
    assert info["cached_tokens"] == 0
    err, s0, s1, c1 = held(prompt, toks, got, page0, page1)
    assert err < LOGIT_ATOL and max(s0, s1, c1) < PAGE_RTOL, \
        (err, s0, s1, c1)


# --------------------------------------------------------------------- #
# 3. The hit across kinds
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_a_stream_resumed_from_a_snapshot_is_one_served_straight_through(
        name):
    eng = engine(name)
    doc = prompt_of(100, 22)                 # leaves a snapshot at 20
    _, _, first, _, _ = through(eng, doc, steps=1)
    assert first["snapshot_at"] == 20
    prompt = np.concatenate([doc[:20], prompt_of(101, 7)])
    toks, got, info, page0, page1 = through(eng, prompt)
    assert info["cached_tokens"] == 20 and info["chunks"] == 1
    assert info["cached_by_class"] == {"latent": 20, "state": 20}
    assert info["lost_to_kind_tokens"] == 0
    err, s0, s1, c1 = held(prompt, toks, got, page0, page1)
    assert err < LOGIT_ATOL and max(s0, s1, c1) < PAGE_RTOL, \
        (err, s0, s1, c1)
    # ... and what it would have read WITHOUT its snapshot is far from it
    low, _, _, _ = held(prompt, toks, got, page0, page1, zero_state_at=20)
    assert low > 100 * LOGIT_ATOL


def test_without_the_snapshot_the_latent_blocks_alone_are_no_hit():
    eng = engine("scarce")
    doc = prompt_of(200, 22)
    through(eng, doc, steps=1)
    # two more streams' snapshots push the document's out of two pages
    through(eng, prompt_of(201, 22), steps=1)
    through(eng, prompt_of(202, 22), steps=1)
    prompt = np.concatenate([doc[:20], prompt_of(203, 7)])
    toks, got, info, page0, page1 = through(eng, prompt)
    assert info["cached_tokens"] == 0
    assert info["cached_by_class"] == {"latent": 0, "state": 0}
    assert info["lost_to_kind_tokens"] == 20     # the blocks were there
    err, s0, s1, c1 = held(prompt, toks, got, page0, page1)
    assert err < LOGIT_ATOL and max(s0, s1, c1) < PAGE_RTOL


# --------------------------------------------------------------------- #
# 4. The shares of an expert layer add up
# --------------------------------------------------------------------- #
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight experts in four shares of two (the configuration's 256 in 16 of
    16): each share's routed part, plus the shared expert ONCE, is the
    reference's layer with every expert held."""
    whole = tiny(held=(0, 8))
    p = seeded(whole, 3)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (24, whole.hidden_size))
    total = jnp.zeros_like(h)
    for first in range(0, 8, 2):
        cut = dataclasses.replace(whole, held=(first, 2))
        part = dict(p, **{k: p[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        y, counts = share.routed_share(part, h, cut.routing, kernel=False)
        assert int(counts.sum()) > 0
        total = total + y
    from deepspeed_tpu.models.blocks import swiglu
    total = total + swiglu(h, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    with jax.default_matmul_precision("highest"):
        want, margin = reference.expert_layer(p, h, sizes_of(whole))
    np.testing.assert_allclose(total, want, atol=2e-5)
    # ... and one share alone is what ``expert_layer`` computes for it
    cut = dataclasses.replace(whole, held=(2, 2))
    part = dict(p, **{k: p[k][2:4] for k in ("w_gate", "w_up", "w_down")})
    y, _ = share.expert_layer(part, h, cut.routing, kernel=False)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_layer(part, h, sizes_of(cut))
    np.testing.assert_allclose(y, want, atol=2e-5)


# --------------------------------------------------------------------- #
# 5. The latent sublayer as one class of several
# --------------------------------------------------------------------- #
def test_a_nope_full_rank_query_serves_through_the_latent_family_too():
    """``models/deepseek_v3.py`` with ``q_lora_rank: null`` and
    ``mla_use_nope``: no ``wq_a`` / ``q_norm`` leaf, nothing rotated — the
    same rows whatever the position."""
    from deepspeed_tpu.models import deepseek_v3 as dsv3
    cfg = dsv3.DeepseekV3Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=2, n_routed_experts=4,
        held=(0, 4), num_experts_per_tok=2, n_group=1, topk_group=1,
        q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, mla_use_nope=True,
        max_position_embeddings=64, dtype=jnp.float32)
    tree = dsv3.deepseek_v3_init(jax.random.PRNGKey(0), cfg)
    assert "wq" in tree["dense"] and "wq_a" not in tree["dense"] \
        and "q_norm" not in tree["dense"]
    p = jax.tree_util.tree_map(lambda a: a[0], tree["dense"])
    h = jax.random.normal(jax.random.PRNGKey(1), (5, cfg.hidden_size))
    a = dsv3.latent_projections(p, h, jnp.arange(5), cfg)
    b = dsv3.latent_projections(p, h, jnp.arange(5) + 17, cfg)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    rotated = dsv3.latent_projections(
        p, h, jnp.arange(5) + 17,
        dataclasses.replace(cfg, mla_use_nope=False))
    assert float(jnp.abs(rotated[3] - a[3]).max()) > 1e-2
    eng = InferenceEngine(cfg, tree, config={"inference": dict(
        max_slots=2, max_seq_len=32, block_size=4, prefill_chunk=8,
        paged_kernel=False)}, mesh=build_mesh(devices=jax.devices()[:1]))
    prompt = np.arange(9, dtype=np.int32)
    tok, _ = eng.prefill(prompt, 0, return_logits=True, max_new_tokens=2)
    assert 0 <= int(tok) < 64
    eng.close()


# --------------------------------------------------------------------- #
# 6. The controls of the benchmark's ``correct``
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("fault", ["no_delta", "head_decay", "unit_alpha",
                                   "rotary_on"])
def test_each_wrong_model_is_far_from_the_served_path(fault):
    eng = engine("chunked")
    prompt = prompt_of(300, 21)
    toks, got, _, page0, page1 = through(eng, prompt)
    err, s0, s1, _ = held(prompt, toks, got, page0, page1)
    low, low_s0, low_s1, _ = held(prompt, toks, got, page0, page1,
                                  fault=fault)
    assert low > 100 * max(err, 1e-6), (fault, low, err)
    if fault != "rotary_on":             # (the first KDA layers are ahead
        assert min(low_s0, low_s1) > 1e-2    # of any latent layer)


def test_a_bfloat16_state_and_8_bit_steps_fail_the_two_links():
    """The links of ``correct``'s rule 3 at the small size: layer 1's steps
    from the program's own pieces against ``first_layer_steps``, and its
    page carried by ``carry_state`` on them."""
    from deepspeed_tpu.models import kimi_linear as kl
    sizes, p = sizes_of(CFG), params()
    tokens = jnp.asarray(prompt_of(400, 40))
    layer = p["layers"][0]
    u = rms_norm(p["embed"][tokens], layer["input_norm"], CFG.rms_norm_eps)
    rows = jnp.concatenate([jnp.zeros((3, CFG.conv_dim)),
                            kl.kda_in(layer, u, CFG)])
    q, k, v = kl.kda_qkv(kl.kda_conv(layer, rows, CFG), CFG)
    g, beta = kl.kda_gates(layer, u, CFG)
    true = reference.first_layer_steps(p, tokens, sizes)
    rough = reference.first_layer_steps(p, tokens, sizes,
                                        act=jnp.float8_e4m3fn)
    for got, want in zip((q, k, v, g, beta), true):
        assert rel(np.asarray(got), np.asarray(want)) < 1e-5
    # (the rule reads the LARGEST of the five: beta, a sigmoid, loses least)
    assert max(rel(np.asarray(low), np.asarray(want))
               for low, want in zip(rough, true)) > 2 ** -6
    S0 = jnp.zeros((2, 16, 16))
    want = reference.carry_state(S0, q, k, v, g, beta)
    low = reference.carry_state(S0, q, k, v, g, beta, cast=jnp.bfloat16)
    _, (state, _) = ref(np.asarray(tokens), [39], state_at=39)
    assert rel(state[0], np.asarray(want)) < 1e-5
    assert rel(np.asarray(low), np.asarray(want)) > 1e-3
    for fault in ("no_delta", "head_decay", "unit_alpha"):
        wrong = reference.carry_state(S0, q, k, v, g, beta, fault=fault)
        assert rel(np.asarray(wrong), np.asarray(want)) > 1e-2, fault


def test_the_references_row_blocks_carry_the_state_and_the_filter_rows(
        monkeypatch):
    """The reference walks a KDA layer in blocks of rows (memory, at 16k
    tokens): the same numbers whatever the block, at a state kept inside a
    later block and past a cut too."""
    tokens = jnp.asarray(np.pad(prompt_of(500, 50), (0, WIDTH - 50)))
    out = jnp.asarray([20, 37, 49])

    def run(**kw):
        lg, _, (S, rows) = reference.forward(
            params(), tokens, sizes_of(CFG), out_positions=out, q_block=16,
            **kw)
        return np.asarray(lg), np.asarray(S), np.asarray(rows)
    whole = [run(state_at=37), run(state_at=33, zero_state_at=30)]
    monkeypatch.setattr(reference, "KDA_BLOCK", 16)
    for want, kw in zip(whole, (dict(state_at=37),
                                dict(state_at=33, zero_state_at=30))):
        for a, b in zip(run(**kw), want):
            np.testing.assert_allclose(a, b, atol=2e-5)


# --------------------------------------------------------------------- #
# 7. A decode step rewrites the filter rows in place (PR 53)
# --------------------------------------------------------------------- #
def test_decode_rewrites_the_filter_rows_in_place_and_serves_the_same(
        monkeypatch):
    """64 KDA heads of 16 make 3,072 filter channels: 24 sublane rows of
    fp32 a held row, whole tiles, so ``served.filter_rows`` hands the decode
    program's rows to ``ops.filter_rows.shift_rows`` (interpret mode here).
    The same engine traced with the shape rule answering no keeps the plain
    lines: the tokens, the logits and both pools of the stream's page are
    equal bit for bit, and the ``decode`` span's arg says which was which."""
    from test_filter_rows import assert_the_same_stream, served_both_ways
    cfg = tiny(kda_num_heads=64, kda_head_dim=16)
    assert filter_tile(cfg.short_conv_kernel_size - 1,
                       cfg.conv_dim) == (1, 72, 128)
    assert_the_same_stream(*served_both_ways(
        monkeypatch, cfg, seeded(cfg), {"latent": 96, "state": 16},
        prompt_of(3, 11), ("conv.state", "state.state")))
    # the file's own size (96 channels: a [3, 96] tile) keeps the plain lines
    assert filter_tile(CFG.short_conv_kernel_size - 1,
                       CFG.conv_dim) == (1, 3, 96)
    assert engine("kernels").filter_rows_in_place == 0

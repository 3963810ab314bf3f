"""The ``solar_open2`` family (Solar-Open2-250B) through the normal serving
path (PR 64): three Kimi-Delta-Attention layers whose write strength reaches
2 to one gated NoPE grouped-query layer that keeps K/V pages, every layer an
expert layer that holds a share of its experts.

What is held to what:
1. What the model declares and the shared code answers: a class of K/V pages
   beside a per-stream class of two dtypes, the layer pattern from
   ``gqa_layers`` (0-based, cut in depth), the registry, no speculation.
2. Served logits and state pages — prefill chunks and decode through both
   kinds of cache, kernels on (interpret mode) and off — against the plain
   float32 reference the benchmark keeps
   (``perfbench/lib/solar_open2_reference.py``: the recurrence token by
   token), with writes over 1 among the test's draws; chunked prefill is the
   one-shot prefill.
3. A hit across kinds (pages by reference + the snapshot the chunk program
   froze) is the cold run; with the snapshot evicted and the pages kept the
   prompt prefills from the last boundary that has one and still agrees.
4. The shares of an expert layer, the shared expert counted once, add up to
   the uncut reference's layer.
5. The controls the benchmark's ``correct`` relies on are far from the
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
from deepspeed_tpu.inference import solar_open2 as serving      # noqa: E402
from deepspeed_tpu.inference.kv_cache import (                  # noqa: E402
    ClassAllocators, class_specs)
from deepspeed_tpu.inference.served import served_model         # noqa: E402
from deepspeed_tpu.models import kimi_linear as kl              # noqa: E402
from deepspeed_tpu.models.blocks import rms_norm, swiglu        # noqa: E402
from deepspeed_tpu.models.solar_open2 import (                  # noqa: E402
    GQA, KDA, SolarOpen2Config, solar_open2_init)
from deepspeed_tpu.moe import share                             # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import solar_open2_reference as reference    # noqa: E402

BS, WIDTH, N_OUT = 8, 64, 4
# fp32 program against the fp32 reference: products at HIGH in the chunks
LOGIT_ATOL, PAGE_RTOL = 3e-4, 3e-5


def tiny(**kw):
    """One period G K K K; 4 KDA heads of 16; 4 : 2 attention heads of 16;
    top-2 of 16 experts, 4 held; 4 taps."""
    base = dict(
        vocab_size=128, hidden_size=64, moe_intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, kda_num_heads=4, kda_head_dim=16, n_routed_experts=16,
        held=(0, 4), num_experts_per_tok=2, max_position_embeddings=256,
        dtype=jnp.float32)
    base.update(kw)
    return SolarOpen2Config(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["linear_attn_config"] = dict(
        num_heads=cfg.kda_num_heads, head_dim=cfg.kda_head_dim,
        short_conv_kernel_size=cfg.short_conv_kernel_size, num_kv_heads=None)
    return d


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights moved off 1, so that a norm
    left out or applied on the wrong side shows."""
    params = solar_open2_init(jax.random.PRNGKey(seed), cfg)
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
    """The file's engines, built once: ``chunked`` (chunks of 16 rows, the
    kernels off), ``kernels`` (the same with the Pallas kernels in interpret
    mode), ``oneshot`` (a chunk as long as the longest prompt), ``scarce`` (a
    state pool of three pages: two streams' snapshots push a third's out
    while its K/V pages stay)."""
    if name not in _MADE:
        conf = dict(max_slots=4, max_seq_len=128, block_size=BS,
                    prefill_chunk=16, paged_kernel=name == "kernels",
                    num_blocks={"full": 64, "state": 12})
        if name == "oneshot":
            conf.update(prefill_chunk=64)
        if name == "scarce":
            conf.update(max_slots=2, num_blocks={"full": 64, "state": 3})
        _MADE[name] = InferenceEngine(
            CFG, params(), config={"inference": conf},
            mesh=build_mesh(devices=jax.devices()[:1]))
    return _MADE[name]


def ref(tokens, positions, state_at=0, zero_state_at=0, fault=None,
        cast=None):
    """(logits, (state, filter rows) at ``state_at``) of the reference, one
    compiled function a variant for rows padded to WIDTH."""
    if ("ref", fault, cast) not in _MADE:
        _MADE["ref", fault, cast] = jax.jit(
            lambda p, t, out, at, cut: reference.forward(
                p, t, sizes_of(CFG), out_positions=out, q_block=16,
                state_at=at, zero_state_at=cut, fault=fault, cast=cast))
    row = np.zeros(WIDTH, np.int32)
    row[:len(tokens)] = tokens
    out = np.zeros(N_OUT, np.int32)
    out[:len(positions)] = positions
    lg, _, states = _MADE["ref", fault, cast](
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
def test_the_layer_pattern_is_read_from_gqa_layers_under_the_depth():
    cfg = SolarOpen2Config.from_hf(
        {"num_hidden_layers": 4, "gqa_layers": list(range(0, 48, 4)),
         "gqa_interval": 3, "linear_attn_config": {
             "num_heads": 64, "head_dim": 128, "short_conv_kernel_size": 4,
             "num_kv_heads": None},
         "n_routed_experts": 320, "use_rope": False, "use_gqa_gate": True,
         "kda_allow_neg_eigval": True, "routed_scaling_factor": 1,
         "model_type": "solar_open2"}, held=(0, 40))
    assert cfg.layer_kinds == (GQA, KDA, KDA, KDA)
    assert (cfg.num_kda_layers, cfg.num_gqa_layers, cfg.num_moe_layers) \
        == (3, 1, 4)
    assert cfg.conv_dim == 3 * 64 * 128 and cfg.group == 8
    r = cfg.routing
    assert (r.experts, r.per_tok, r.n_group, r.topk_group, r.norm, r.scale,
            r.held, r.rule) == (320, 8, 1, 1, True, 1.0, (0, 40),
                                "sigmoid_bias")
    with pytest.raises(NotImplementedError, match="no position encoding"):
        tiny(use_rope=True)
    with pytest.raises(ValueError, match="no share"):
        tiny(held=(14, 4))


def test_k_v_pages_stand_beside_a_per_stream_class_of_two_dtypes():
    served = served_model(tiny(dtype=jnp.bfloat16))
    assert isinstance(served, serving.SolarOpen2Served)
    full, state = served.cache_classes
    assert (full.name, full.layers, full.reach, full.per_stream) \
        == ("full", 1, None, False)
    assert (state.name, state.layers, state.per_stream) == ("state", 3, True)
    specs = class_specs(
        served.cache_classes, {"full": 24, "state": 6}, rows=16,
        of_class=lambda cls: served.class_geometry(cls, BS),
        num_slots=4, block_size=BS, max_len=128, num_groups=1,
        dtype=jnp.bfloat16)
    assert specs[0].pool_dtypes == {"k.full": jnp.bfloat16,
                                    "v.full": jnp.bfloat16}
    assert specs[1].pool_dtypes == {"state.state": jnp.float32,
                                    "conv.state": jnp.bfloat16}
    assert specs[1].pool_shapes["state.state"] == (3, 1, 6, 4, 16, 16)
    # a block: K and V rows of 2 heads of 16 a token, one layer
    assert specs[0].block_nbytes() == 2 * BS * 2 * 16 * 2
    # a page's bytes count each pool in its own dtype
    assert specs[1].block_nbytes() == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    # a KDA layer's share of what a token keeps as K/V rows
    assert specs[1].token_row_bytes == -(-(2 * 2 * 16 * 2) // 3)
    assert isinstance(engine("chunked").allocator, ClassAllocators)
    assert engine("chunked").served.table_widths == (16, 1)


def test_a_state_cannot_be_rolled_back_so_speculation_is_refused():
    with pytest.raises(ValueError, match="spec_k"):
        InferenceEngine(CFG, params(), config={"inference": dict(
            max_slots=2, max_seq_len=64, block_size=BS, prefill_chunk=16,
            spec_k=2)}, mesh=build_mesh(devices=jax.devices()[:1]))
    with pytest.raises(NotImplementedError, match="rolled back"):
        served_model(CFG).verify(None, None, None, None, None,
                                 num_groups=1, paged_kernel=False)


def test_nothing_of_the_family_is_imported_unless_a_configuration_asks():
    import subprocess
    code = """
import sys
import deepspeed_tpu
from deepspeed_tpu.inference import InferenceEngine, served
from deepspeed_tpu.models import GPT2_CONFIGS
served.served_model(GPT2_CONFIGS['gpt2-tiny'])
bad = [m for m in sys.modules if m.startswith('deepspeed_tpu.') and
       m.rsplit('.', 1)[-1] in ('solar_open2', 'kimi_linear', 'kda_state',
                                'kda', 'kv_pages')]
print('LOADED', bad)
from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
print(type(served.served_model(SolarOpen2Config())).__name__)
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu",
                                  PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LOADED []" in out.stdout and "SolarOpen2Served" in out.stdout


def test_the_write_strength_really_passes_one():
    """``beta`` = 2 sigmoid(.) over the test's own draws: about half the
    writes land over 1, none at or over 2; the same pieces under a config
    without the key stay under 1."""
    p = params()["layers"][1]
    tokens = jnp.asarray(prompt_of(7, 48))
    u = rms_norm(params()["embed"][tokens], p["input_norm"],
                 CFG.rms_norm_eps)
    _, beta = kl.kda_gates(p, u, CFG)
    assert 0.25 < float((beta > 1).mean()) < 0.75
    assert float(beta.max()) < 2 and float(beta.min()) > 0
    plain = dataclasses.replace(CFG, kda_allow_neg_eigval=False)
    _, under = kl.kda_gates(p, u, plain)
    np.testing.assert_allclose(2 * under, beta, rtol=1e-6)


# --------------------------------------------------------------------- #
# 2. Through both kinds of cache against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["chunked", "kernels", "oneshot"])
@pytest.mark.parametrize("n", [37, 16, 5])
def test_prefill_then_decode_through_both_kinds_is_the_reference(name, n):
    """37 tokens: three chunk programs, the state carried from one to the
    next (one program in ``oneshot``: chunked prefill is the one shot); 16:
    one whole chunk; 5: a chunk with dead rows."""
    eng = engine(name)
    prompt = prompt_of(n, n)
    toks, got, info, page0, page1 = through(eng, prompt)
    assert info["cached_tokens"] == 0
    assert info["chunks"] == (1 if name == "oneshot" else -(-n // 16))
    err, s0, s1, c1 = held(prompt, toks, got, page0, page1)
    assert err < LOGIT_ATOL and max(s0, s1, c1) < PAGE_RTOL, \
        (err, s0, s1, c1)


# --------------------------------------------------------------------- #
# 3. The hit across kinds
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_a_hit_across_kinds_then_decode_is_the_cold_run(name):
    eng = engine(name)
    doc = prompt_of(100, 35)                 # leaves a snapshot at 32
    _, _, first, _, _ = through(eng, doc, steps=1)
    assert first["snapshot_at"] == 32
    prompt = np.concatenate([doc[:32], prompt_of(101, 9)])
    toks, got, info, page0, page1 = through(eng, prompt)
    assert info["cached_tokens"] == 32 and info["chunks"] == 1
    assert info["cached_by_class"] == {"full": 32, "state": 32}
    assert info["lost_to_kind_tokens"] == 0
    err, s0, s1, c1 = held(prompt, toks, got, page0, page1)
    assert err < LOGIT_ATOL and max(s0, s1, c1) < PAGE_RTOL, \
        (err, s0, s1, c1)
    # ... the cold run of the same prompt on the one-shot engine agrees
    cold_toks, cold, cold_info, _, _ = through(engine("oneshot"), prompt)
    assert cold_info["cached_tokens"] == 0 and cold_toks == toks
    np.testing.assert_allclose(got, cold, atol=2 * LOGIT_ATOL)
    # ... and what it would have read WITHOUT its snapshot is far from it
    low, _, _, _ = held(prompt, toks, got, page0, page1, zero_state_at=32)
    assert low > 100 * LOGIT_ATOL


def test_a_snapshot_evicted_with_its_pages_kept_prefills_from_an_earlier_one():
    """Three pages, one of them the live stream's: a long prompt leaves its
    snapshot at 48; prompts that share its first 16 tokens leave one at 32
    (of their own tokens) and one at 16, and push the first out.  The pages
    of all 48 tokens are still there, the LAST boundary that has a snapshot
    is 16: the prompt resumes there in both classes, the 32 tokens whose
    pages were lost to the other kind are counted, and the logits still
    agree."""
    eng = engine("scarce")
    doc = prompt_of(200, 51)
    _, _, first, _, _ = through(eng, doc, steps=1)
    assert first["snapshot_at"] == 48
    prompt = np.concatenate([doc[:48], prompt_of(203, 7)])
    assert eng.prefix_match_tokens(prompt) == 48
    _, _, second, _, _ = through(eng, np.concatenate(
        [doc[:16], prompt_of(204, 20)]), steps=1)
    assert second["cached_tokens"] == 0 and second["snapshot_at"] == 32
    assert second["lost_to_kind_tokens"] == 16   # pages at 16, no snapshot
    _, _, third, _, _ = through(eng, np.concatenate(
        [doc[:16], prompt_of(205, 2)]), steps=1)
    assert third["snapshot_at"] == 16
    assert eng.allocator.snapshot_totals()["snapshots_evicted"] == 1
    assert eng.prefix_match_tokens(prompt) == 16
    toks, got, info, page0, page1 = through(eng, prompt)
    assert info["cached_tokens"] == 16 and info["cow_fork"]
    assert info["cached_by_class"] == {"full": 16, "state": 16}
    assert info["lost_to_kind_tokens"] == 32 and info["snapshot_at"] == 48
    err, s0, s1, c1 = held(prompt, toks, got, page0, page1)
    assert err < LOGIT_ATOL and max(s0, s1, c1) < PAGE_RTOL


# --------------------------------------------------------------------- #
# 4. The shares of an expert layer add up
# --------------------------------------------------------------------- #
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen experts in four shares of four (the configuration's 320 in 8
    of 40): each share's routed part, plus the shared expert ONCE, is the
    reference's layer with every expert held."""
    whole = tiny(held=(0, 16))
    p = seeded(whole, 3)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (24, whole.hidden_size))
    total = jnp.zeros_like(h)
    for first in range(0, 16, 4):
        cut = dataclasses.replace(whole, held=(first, 4))
        part = dict(p, **{k: p[k][first:first + 4]
                          for k in ("w_gate", "w_up", "w_down")})
        y, counts = share.routed_share(part, h, cut.routing, kernel=False)
        assert int(counts.sum()) > 0
        total = total + y
    total = total + swiglu(h, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    with jax.default_matmul_precision("highest"):
        want, margin = reference.expert_layer(p, h, sizes_of(whole))
    np.testing.assert_allclose(total, want, atol=2e-5)
    # ... and one share alone is what ``expert_layer`` computes for it
    cut = dataclasses.replace(whole, held=(4, 4))
    part = dict(p, **{k: p[k][4:8] for k in ("w_gate", "w_up", "w_down")})
    y, _ = share.expert_layer(part, h, cut.routing, kernel=False)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_layer(part, h, sizes_of(cut))
    np.testing.assert_allclose(y, want, atol=2e-5)


# --------------------------------------------------------------------- #
# 5. The controls of the benchmark's ``correct``
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("variant", [
    dict(fault="no_two"), dict(fault="no_gate"),
    dict(cast=jnp.float8_e4m3fn)], ids=["no_two", "no_gate", "e4m3"])
def test_each_wrong_model_is_far_from_the_served_path(variant):
    eng = engine("chunked")
    prompt = prompt_of(300, 37)
    toks, got, _, page0, page1 = through(eng, prompt)
    err, s0, s1, _ = held(prompt, toks, got, page0, page1)
    low, low_s0, low_s1, _ = held(prompt, toks, got, page0, page1, **variant)
    assert low > 100 * max(err, 1e-6), (variant, low, err)
    assert min(low_s0, low_s1) > 100 * max(s0, s1, 1e-7)

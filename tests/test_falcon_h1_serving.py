"""The ``falcon_h1`` family (Falcon-H1-34B-Instruct) through the normal
serving path (PR 48): EVERY layer runs a Mamba-2 state-space mixer (an fp32
state a stream) and grouped-query attention (K/V pages a token) side by side,
two KINDS of cache of two dtypes in one layer and one manager.

What is held to what:
1. The three forms of the recurrence (``ops/ssm_scan.py``) to each other:
   the chunked scan against the token-by-token update across chunk edges,
   from a carried state, past dead rows and at the state it keeps; the
   decode kernel (interpret mode) against the plain update, dead slots
   included.
2. Served logits and state pages — prefill chunks and decode through both
   kinds of cache, a second request through the prefix-hit path (pages by
   reference + a snapshot left by the chunk program) — against the plain
   float32 reference the benchmark keeps
   (``perfbench/lib/falcon_h1_reference.py``), kernels on and off.
3. The prefix rule on this family: a hit needs the pages AND a snapshot;
   with the snapshot reclaimed the same prompt falls back to 0 and the loss
   is counted.
4. What the shared code answers: both classes of ALL the layers, pools of
   two dtypes from one ``class_specs`` call, 5 query rows a K/V head in the
   gather path and in the kernel's plan.
5. The controls the benchmark's ``correct`` relies on are far from the
   served path.
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
from deepspeed_tpu.inference.kv_cache import (                  # noqa: E402
    ClassAllocators, StateAllocator, class_specs, init_paged_cache)
from deepspeed_tpu.inference.kv_pages import attend_rows        # noqa: E402
from deepspeed_tpu.inference.served import (                    # noqa: E402
    filter_tile, served_model)
from deepspeed_tpu.models.falcon_h1 import (                    # noqa: E402
    FalconH1Config, falcon_h1_init)
from deepspeed_tpu.ops import paged_attention as paged_attn_ops  # noqa: E402
from deepspeed_tpu.ops import ssm_scan                          # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import falcon_h1_reference as reference      # noqa: E402

BS, WIDTH, N_OUT = 4, 64, 6
# fp32 program against the fp32 reference: products at HIGH in the scan
LOGIT_ATOL, PAGE_RTOL = 2e-4, 2e-5


def tiny(**kw):
    """2 layers; 10 query heads over 2 K/V heads of 16 (5 a K/V head); 4
    state heads of 8 in 2 groups, 16 state dimensions, 4 taps; the published
    multipliers' kinds, none of them 1."""
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=10, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=8, max_position_embeddings=256, rope_theta=1e4,
        embedding_multiplier=5.66, lm_head_multiplier=0.0078,
        attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
        key_multiplier=0.011, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.088, ssm_multipliers=(0.35, 0.25, 0.18, 0.5,
                                                   0.35),
        mlp_multipliers=(0.18, 0.011), ssm_dt_range=(0.01, 0.3),
        dtype=jnp.float32)
    base.update(kw)
    return FalconH1Config(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights and D moved off 1, so that a
    norm left out or applied on the wrong side shows."""
    params = falcon_h1_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        a * jnp.asarray(rng.uniform(0.6, 1.4, a.shape), a.dtype)
        if "norm" in str(path[-1]) or str(path[-1]) == "['D']" else a
        for path, a in leaves])


CFG = tiny()
_MADE = {}


def params():
    if "params" not in _MADE:
        _MADE["params"] = seeded(CFG)
    return _MADE["params"]


def engine(name):
    """The file's engines, built once: ``chunked`` (chunks of 8 rows, the
    kernels off), ``kernels`` (the same with the Pallas kernels in interpret
    mode), ``scarce`` (a state pool of two pages: a second stream's
    snapshot pushes the first out)."""
    if name not in _MADE:
        conf = dict(max_slots=4, max_seq_len=128, block_size=BS,
                    prefill_chunk=8, paged_kernel=name == "kernels",
                    num_blocks={"full": 96, "state": 16})
        if name == "scarce":
            conf.update(max_slots=2, num_blocks={"full": 96, "state": 2})
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
    lg, states = _MADE["ref", fault](
        params(), jnp.asarray(row), jnp.asarray(out), jnp.int32(state_at),
        jnp.int32(zero_state_at))
    return np.asarray(lg)[:len(positions)], \
        tuple(np.asarray(s) for s in states)


def page_of(eng, slot):
    """The stream's page, every layer: (state [L, nh, N, P], filter rows
    [L, taps - 1, conv_dim])."""
    page = int(eng.block_tables[slot][-1])
    ssm = np.asarray(eng.cache["ssm.state"])[:, 0, page]
    conv = np.asarray(eng.cache["conv.state"])[:, 0, page]
    return ssm, conv.reshape(conv.shape[0], CFG.mamba_d_conv - 1,
                             CFG.conv_dim)


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
# 1. The three forms of the recurrence
# --------------------------------------------------------------------- #
def _scan_case(seed, T=24, nh=4, G=2, N=16, Pd=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[3], (T, nh)) - 2)
    A = -jnp.exp(jax.random.normal(k[4], (nh,)))
    return dict(x=jax.random.normal(k[0], (T, nh, Pd)),
                B=jax.random.normal(k[1], (T, G, N)),
                C=jax.random.normal(k[2], (T, G, N)), dt=dt, a=dt * A,
                S0=jax.random.normal(k[5], (nh, N, Pd)))


def _token_by_token(c, rows):
    S, ys, states = c["S0"], [], []
    for t in range(rows):
        y, S = ssm_scan.recurrent_update(
            S[None], c["x"][t][None], c["B"][t][None], c["C"][t][None],
            c["dt"][t][None], jnp.exp(c["a"][t])[None])
        S = S[0]
        ys.append(y[0])
        states.append(S)
    return jnp.stack(ys), states


@pytest.mark.parametrize("chunk", [4, 8, 24])
def test_the_chunked_scan_is_the_recurrence_across_chunk_edges(chunk):
    c = _scan_case(0)
    ys, states = _token_by_token(c, 24)
    with jax.default_matmul_precision("highest"):
        y, S, kept = ssm_scan.chunked_scan(
            c["S0"], c["x"], c["B"], c["C"], c["dt"], c["a"], chunk=chunk,
            keep=jnp.int32(24 // chunk - 1 if chunk > 8 else 1))
    np.testing.assert_allclose(y, ys, atol=2e-5)
    np.testing.assert_allclose(S, states[-1], atol=2e-6)
    at = 23 if chunk > 8 else 2 * chunk - 1
    np.testing.assert_allclose(kept, states[at], atol=2e-6)


def test_dead_rows_neither_decay_the_state_nor_add_to_it():
    c = _scan_case(1)
    ys, states = _token_by_token(c, 19)
    live = (jnp.arange(24) < 19)[:, None]
    with jax.default_matmul_precision("highest"):
        y, S, _ = ssm_scan.chunked_scan(
            c["S0"], c["x"], c["B"], c["C"], c["dt"] * live, c["a"] * live,
            chunk=8)
    np.testing.assert_allclose(y[:19], ys, atol=2e-5)
    np.testing.assert_allclose(S, states[18], atol=2e-6)


def test_the_scan_refuses_rows_that_are_not_whole_chunks():
    c = _scan_case(2)
    with pytest.raises(ValueError, match="sub-chunks"):
        ssm_scan.chunked_scan(c["S0"], c["x"], c["B"], c["C"], c["dt"],
                              c["a"], chunk=7)


@pytest.mark.parametrize("pages", [[3, -1, 0, 5, -1], [-1, -1, -1, -1, -1],
                                   [1, 2, 3, 4, 5]])
def test_the_decode_kernel_is_the_plain_update_in_place(pages):
    c = _scan_case(3)
    nh, N, Pd = c["S0"].shape
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 1, 6, nh, N, Pd))
    pages = jnp.asarray([pages], jnp.int32)
    x, B, C = c["x"][:5][None], c["B"][:5][None], c["C"][:5][None]
    dt, da = c["dt"][:5][None], jnp.exp(c["a"][:5])[None]
    y, new = jax.jit(ssm_scan.state_update)(pool, 1, pages, x, B, C, dt, da)
    live = np.asarray(pages[0] >= 0)
    at = jnp.maximum(pages[0], 0)
    want_y, want_S = ssm_scan.recurrent_update(pool[1, 0, at], x[0], B[0],
                                               C[0], dt[0], da[0])
    np.testing.assert_allclose(np.asarray(y[0])[live],
                               np.asarray(want_y)[live], atol=1e-5)
    assert not np.asarray(y[0])[~live].any()
    want = np.array(pool)
    want[1, 0, np.asarray(at)[live]] = np.asarray(want_S)[live]
    np.testing.assert_allclose(new, want, atol=1e-6)   # other pages as were


def test_the_kernel_tiles_heads_of_one_group_under_its_budget():
    assert ssm_scan.tile_heads(32, 2, 256, 128) == 16        # 2 MB a step
    assert ssm_scan.tile_heads(4, 2, 16, 8) == 2
    assert ssm_scan.state_update_steps(3, 8, 32, 2, 256, 128) == (16, 6)


# --------------------------------------------------------------------- #
# 2. What the model declares and the shared code answers
# --------------------------------------------------------------------- #
def test_both_classes_hold_all_the_layers_in_pools_of_two_dtypes():
    served = served_model(tiny(dtype=jnp.bfloat16))
    full, state = served.cache_classes
    assert (full.name, full.layers, full.per_stream) == ("full", 2, False)
    assert (state.name, state.layers, state.per_stream) == ("state", 2, True)
    specs = class_specs(
        served.cache_classes, {"full": 24, "state": 6}, rows=8,
        of_class=lambda cls: served.class_geometry(cls, BS),
        num_slots=4, block_size=BS, max_len=128, num_groups=1,
        dtype=jnp.bfloat16)
    assert specs[0].pool_dtypes == {"k.full": jnp.bfloat16,
                                    "v.full": jnp.bfloat16}
    assert specs[1].pool_dtypes == {"ssm.state": jnp.float32,
                                    "conv.state": jnp.bfloat16}
    assert specs[1].pool_shapes["ssm.state"] == (2, 1, 6, 4, 16, 8)
    # a page's bytes count each pool in its own dtype
    ssm, conv = 4 * 16 * 8, 3 * served.cfg.conv_dim
    assert specs[1].block_nbytes() == 2 * (ssm * 4 + conv * 2)
    # The byte rule alone (the page against a token's K/V rows of a layer)
    # asks for 21 tokens; beside classes of pages a prompt that adds one
    # prefill program's rows is worth a snapshot whatever the page's bytes.
    assert specs[1].token_row_bytes == 2 * 2 * 16 * 2
    assert dataclasses.replace(specs[1], program_rows=0).page_tokens == 21
    assert specs[1].program_rows == 8 and specs[1].page_tokens == 8
    assert specs[0].program_rows == 0
    pools = {}
    for spec in specs:
        pools.update(init_paged_cache(spec))
    assert {n: p.dtype for n, p in pools.items()} == {
        "k.full": jnp.bfloat16, "v.full": jnp.bfloat16,
        "ssm.state": jnp.float32, "conv.state": jnp.bfloat16}
    assert isinstance(engine("chunked").allocator, ClassAllocators)


def test_the_retention_family_answers_its_dtype_the_same_way():
    from deepspeed_tpu.inference.retention import RetentionServed
    from deepspeed_tpu.models.brumby import BrumbyConfig
    served = RetentionServed(BrumbyConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        head_dim=16))
    assert {pool[2] for pool in served.cache_pools(8)} == {jnp.float32}
    (spec,) = class_specs(
        served.cache_classes, 4, rows=8,
        of_class=lambda c: served.class_geometry(c, 8), num_slots=2,
        block_size=8, max_len=64, num_groups=1, dtype=jnp.bfloat16)
    assert set(spec.pool_dtypes.values()) == {jnp.float32}
    # the model's only cache: the byte rule stands alone
    assert spec.program_rows == 0


@pytest.mark.parametrize("rows, alone, beside", [(8, 21, 8), (16, 21, 16),
                                                 (64, 21, 21)])
def test_beside_pages_a_whole_programs_rows_are_worth_a_snapshot(
        rows, alone, beside):
    """``page_tokens``: the byte rule for a state that is a model's only
    cache; beside classes of pages, one prefill program's rows at most."""
    served = served_model(tiny(dtype=jnp.bfloat16))
    geometry = dict(num_slots=4, block_size=BS, max_len=128, num_groups=1,
                    dtype=jnp.bfloat16)
    both = class_specs(served.cache_classes, {"full": 24, "state": 6},
                       rows=rows, of_class=lambda c: served.class_geometry(
                           c, BS), **geometry)
    only = class_specs(served.cache_classes[1:], 6, rows=rows,
                       of_class=lambda c: served.class_geometry(c, BS),
                       **geometry)
    assert only[0].page_tokens == alone and both[1].page_tokens == beside
    for spec, tokens in ((only[0], alone), (both[1], beside)):
        alloc = StateAllocator(spec)
        assert alloc.snapshot_boundary(tokens + BS, 0) == tokens // BS * BS \
            + BS
        assert alloc.snapshot_boundary(BS + tokens - 1, BS) == 0


@pytest.mark.parametrize("n, first", [(18, 8), (23, 16), (21, 3)])
def test_the_references_steps_carry_a_page_to_the_page_after(n, first):
    """What the benchmark's state rule stands on (``runners/chat_state``
    rule 3): layer 0 of a served page is the reference's recurrence
    (``carry_state``) over the reference's OWN layer-0 steps
    (``first_layer_steps``: from the weights alone), from position 0 and
    from the reference's state part way."""
    prompt = prompt_of(60 + n, n)
    _, _, _, page0, _ = through(engine("chunked"), prompt, steps=0)
    sizes = sizes_of(CFG)
    _, (before, _) = ref(prompt, [n - 1], state_at=first - 1)
    skip = CFG.mamba_d_conv - 1
    x, B, dt, decay = reference.first_layer_steps(
        params(), jnp.asarray(prompt[first - skip:]), sizes, skip=skip)
    want = reference.carry_state(before[0], x, B, dt, decay)
    assert rel(page0[0][0], np.asarray(want)) <= 1e-5
    # ... and from nothing at position 0
    x, B, dt, decay = reference.first_layer_steps(
        params(), jnp.asarray(prompt), sizes)
    zero = jnp.zeros_like(before[0])
    assert rel(page0[0][0], np.asarray(
        reference.carry_state(zero, x, B, dt, decay))) <= 1e-5
    low = reference.carry_state(zero, x, B, dt, decay, cast=jnp.bfloat16)
    assert rel(np.asarray(low), page0[0][0]) > 1e-3


def test_the_published_page_is_what_the_issue_counted():
    cfg = FalconH1Config(num_hidden_layers=4)
    served = served_model(cfg)
    geo = served.class_geometry(served.cache_classes[1], 64)
    assert geo["pools"][0] == ("ssm", (32, 256, 128), jnp.float32)
    assert geo["pools"][1] == ("conv", (1, 120, 128))
    assert cfg.ssm_in_width == 9248 and cfg.conv_dim == 5120
    # a token's K/V rows of a layer: a page is 2,063 tokens' worth
    assert geo["token_row_bytes"] == 2 * 4 * 128 * 2


def test_verify_raises_and_speculation_is_refused():
    served = served_model(CFG)
    with pytest.raises(NotImplementedError, match="spec_k"):
        served.verify(None, None, None, None, None, num_groups=1,
                      paged_kernel=False)
    with pytest.raises(ValueError, match="spec_k"):
        InferenceEngine(CFG, params(), config={"inference": dict(
            max_slots=2, max_seq_len=64, block_size=BS, prefill_chunk=8,
            spec_k=2, num_blocks={"full": 8, "state": 4})},
            mesh=build_mesh(devices=jax.devices()[:1]))


def test_five_query_rows_a_head_through_the_gather_and_the_plan():
    assert CFG.group == 5 and FalconH1Config().group == 5
    # decode: 5 rows a K/V head; a prefill chunk of 512 in runs of 64
    assert attend_rows(1, 5) == 1
    assert attend_rows(512, 5) == 64
    pool = jnp.zeros((2, 1, 8, 2, BS * 16 // 128 or 1, 128), jnp.float32)
    bt = jnp.asarray([[[0, 1, -1], [2, -1, -1]]], jnp.int32)
    seen = jnp.asarray([[[5], [2]]], jnp.int32)
    plan = paged_attn_ops.attend_plan(bt, seen, pool, 16, group=5)
    assert plan is not None


# --------------------------------------------------------------------- #
# 3. Served logits and pages against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["chunked", "kernels"])
@pytest.mark.parametrize("n", [5, 8, 21])
def test_prefill_and_decode_agree_with_the_reference(name, n):
    """A prompt shorter than a chunk, of one chunk and of three (the last
    one padded): logits, the state and the filter rows."""
    prompt = prompt_of(n, n)
    toks, got, info, page0, page1 = through(engine(name), prompt)
    assert info["cached_tokens"] == 0 and info["chunks"] == -(-n // 8)
    err, s0, s1, c1 = held(prompt, toks, got, page0, page1)
    assert err <= LOGIT_ATOL and max(s0, s1, c1) <= PAGE_RTOL, \
        (err, s0, s1, c1)


@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_a_hit_needs_pages_and_a_snapshot_and_resumes_from_both(name):
    """A prompt of 18 tokens leaves its blocks and a snapshot at 16, written
    by the chunk program that passed the boundary (no cut, no copy); a
    prompt that extends it resumes at 16 in BOTH classes."""
    eng = engine(name)
    taken0 = eng.allocator.snapshot_totals()["snapshots_taken"]
    first = prompt_of(40, 18)
    _, _, info, _, _ = through(eng, first, steps=1)
    assert info["chunks"] == 3 and info.get("snapshot_at") == 16
    totals = eng.allocator.snapshot_totals()
    assert totals["snapshots_taken"] == taken0 + 1
    longer = np.concatenate([first[:17], prompt_of(41, 9)])
    toks, got, info, page0, page1 = through(eng, longer)
    assert info["cached_tokens"] == 16 and info["cow_fork"]
    assert info["cached_by_class"] == {"full": 16, "state": 16}
    assert info["lost_to_kind_tokens"] == 0
    err, s0, s1, c1 = held(longer, toks, got, page0, page1)
    assert err <= LOGIT_ATOL and max(s0, s1, c1) <= PAGE_RTOL, \
        (err, s0, s1, c1)
    # the wrong model: a stream resumed WITHOUT its snapshot
    bad = held(longer, toks, got, page0, page1, zero_state_at=16)
    assert bad[0] > 50 * LOGIT_ATOL and bad[1] > 0.05


def test_the_snapshot_is_the_scans_own_state_at_the_boundary():
    """The page the chunk program froze at 16 is the reference's state
    there: the scan's carried state, not a second accumulation."""
    eng = engine("chunked")
    prompt = prompt_of(50, 23)
    through(eng, prompt, steps=0)
    n, page, _ = eng.allocator.classes[1].match_snapshot(
        0, np.concatenate([prompt, [0]]))
    assert n == 5                                   # boundary 20 = 5 blocks
    ssm = np.asarray(eng.cache["ssm.state"])[:, 0, page]
    conv = np.asarray(eng.cache["conv.state"])[:, 0, page].reshape(
        CFG.num_hidden_layers, CFG.mamba_d_conv - 1, CFG.conv_dim)
    _, (want_s, want_c) = ref(prompt, [22], state_at=19)
    assert rel(ssm, want_s) <= PAGE_RTOL and rel(conv, want_c) <= PAGE_RTOL


def test_a_reclaimed_snapshot_falls_back_to_zero_and_is_counted():
    """A state pool of two pages: a second prompt's snapshot pushes the
    first one out; the first prompt's blocks are still cached, so what the
    pages had is LOST TO KIND, and the prompt prefills from 0."""
    eng = engine("scarce")
    a, b = prompt_of(60, 18), prompt_of(61, 18)
    through(eng, a, steps=0)
    again = np.concatenate([a[:17], prompt_of(62, 5)])
    assert eng.prefix_match_tokens(again) == 16
    through(eng, b, steps=0)
    assert eng.prefix_match_tokens(again) == 0
    toks, got, info, page0, page1 = through(eng, again)
    assert info["cached_tokens"] == 0
    assert info["lost_to_kind_tokens"] == 16
    assert eng.serving.snapshot()["state"]["prefix_lost_to_kind_tokens"] >= 16
    err, s0, s1, c1 = held(again, toks, got, page0, page1)
    assert err <= LOGIT_ATOL and max(s0, s1, c1) <= PAGE_RTOL


def test_batched_decode_keeps_the_streams_apart():
    """Three streams of different lengths decode together, one slot dead:
    each one's logits are its own reference's."""
    eng = engine("kernels")
    prompts = [prompt_of(70 + i, n) for i, n in enumerate((6, 13, 9))]
    slots, seqs = [], []
    for p in prompts:
        slot = eng.select_slot(p, 3)
        tok, _ = eng.prefill(p, slot, return_logits=True, max_new_tokens=3)
        eng.activate_slot(slot, len(p), tok)
        slots.append(slot)
        seqs.append(list(p) + [tok])
    for _ in range(2):
        sampled, lg = eng.decode_once(return_logits=True)
        for slot, seq in zip(slots, seqs):
            want, _ = ref(np.asarray(seq), [len(seq) - 1])
            assert np.abs(np.asarray(lg[slot]) - want[0]).max() <= LOGIT_ATOL
            seq.append(int(sampled[slot]))
    for slot in slots:
        eng.release_slot(slot)


# --------------------------------------------------------------------- #
# 4. The controls
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("fault", ["no_ssm", "d_zero",
                                   "unit_ssm_multipliers"])
def test_each_wrong_model_is_far_from_the_served_path(fault):
    prompt = prompt_of(80, 21)
    toks, got, _, page0, page1 = through(engine("chunked"), prompt)
    assert held(prompt, toks, got, page0, page1)[0] <= LOGIT_ATOL
    assert held(prompt, toks, got, page0, page1, fault=fault)[0] \
        > 100 * LOGIT_ATOL


def test_a_bfloat16_state_fails_the_page_and_passes_nothing_else_by_luck():
    prompt = prompt_of(81, 40)
    toks, got, _, page0, page1 = through(engine("chunked"), prompt)
    err, s0, s1, _ = held(prompt, toks, got, page0, page1,
                          fault="bf16_state")
    assert s0 > 50 * PAGE_RTOL and s1 > 50 * PAGE_RTOL


def test_the_init_gives_every_branch_a_say():
    """Under the published multipliers the seeded init leaves unit-variance
    scores and logits and a state that moves ``y`` beside ``D x`` (module
    docstring of ``falcon_h1_init``)."""
    prompt = prompt_of(82, 40)
    lg, _ = ref(prompt, [39])
    assert 0.3 < float(np.std(lg)) < 3.0
    drop, _ = ref(prompt, [39], fault="no_ssm")
    assert float(np.abs(lg - drop).max()) > 0.1


# --------------------------------------------------------------------- #
# A decode step rewrites the filter rows in place where the shape allows
# (PR 53)
# --------------------------------------------------------------------- #
def test_decode_rewrites_the_filter_rows_in_place_and_serves_the_same(
        monkeypatch):
    """4 state heads of 128 and 2 groups of 128 state dimensions make 1,024
    filter channels: 8 sublane rows of fp32 a held row, a whole tile, so
    ``served.filter_rows`` hands the decode program's rows to
    ``ops.filter_rows.shift_rows`` (interpret mode here).  The same engine
    traced with the shape rule answering no keeps the plain lines: the
    tokens, the logits and both pools of the stream's page are equal bit for
    bit, and the ``decode`` span's arg says which was which.  The PUBLISHED
    width (5,120 channels of bf16: 40 sublane rows a held row, two and a
    half tiles) is one the rule leaves on the plain lines."""
    from deepspeed_tpu.ops import filter_rows as in_place
    from test_filter_rows import assert_the_same_stream, served_both_ways
    cfg = tiny(mamba_d_ssm=512, mamba_d_head=128, mamba_d_state=128)
    assert cfg.conv_dim == 1024 and filter_tile(
        cfg.mamba_d_conv - 1, cfg.conv_dim) == (1, 24, 128)
    assert not in_place.takes((4, 1, 184, 1, 120, 128), jnp.bfloat16, 3,
                              jnp.bfloat16)
    assert_the_same_stream(*served_both_ways(
        monkeypatch, cfg, seeded(cfg), {"full": 96, "state": 16},
        prompt_of(3, 11), ("conv.state", "ssm.state")))
    # the file's own size (96 channels: a [3, 96] tile) keeps the plain lines
    assert engine("kernels").filter_rows_in_place == 0

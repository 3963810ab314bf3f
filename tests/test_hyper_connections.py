"""The ``deepseek_v3`` family on several residual streams (PR 41, the
``xing4_0`` keys): manifold-constrained hyper-connections round every
latent-attention and FFN / expert sublayer, through the normal serving
path.

What is held to what:
1. Served logits — prefill, then decode through the paged latent cache,
   then a request through the prefix-hit path — against the plain float32
   reference the benchmark keeps (``perfbench/lib/xing_reference.py``),
   kernels on and off, ``hc_mult`` 4 and 2, on seeded weights whose maps
   are ALIVE (``alpha`` 1, ``b`` normal(0, 1)); the reference's two wrong
   models (``H_res`` = I, one Sinkhorn iteration) do NOT agree.
2. The maps: ``H_res`` rows and columns sum to 1, also at the clamp; the
   mixes written as a literal per-token loop equal the vectorised ones.
3. The maps are per token, so nothing new enters the cache: one chunk,
   three chunks and a prefix hit give the same cache rows and logits; a
   batch through the scheduler equals one by one.
4. A config without the keys builds today's parameter tree and programs
   with no ``hc`` scope; ``from_hf`` takes the five keys from the published
   dict; scopes and the ``hc_res_err_max`` counter are where the docs say.

All 64 experts of the benchmark's configuration are held, so the
``model-configs`` guide's shares-add-up test has nothing to tie here (the
share path itself is ``tests/test_latent_serving.py``'s).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import InferenceEngine              # noqa: E402
from deepspeed_tpu.inference.scheduler import Request            # noqa: E402
from deepspeed_tpu.models import hyper_connections as hyper      # noqa: E402
from deepspeed_tpu.models.deepseek_v3 import (                   # noqa: E402
    DeepseekV3Config, deepseek_v3_init)
from deepspeed_tpu.ops import latent_attention as la             # noqa: E402
from perfbench.lib import xing_reference as reference            # noqa: E402
from test_latent_serving import (                                # noqa: E402
    _serve_one, one_device, sizes_of, tiny)
from test_program_spans import _op_names                         # noqa: E402

# The published config.json of Xing4.0-29B-A4B (the catalog's copy).
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


def xing_tiny(hc_mult=4, **kw):
    """``tiny`` with Xing's routing (one group, top-4) and the residual
    maps.  ``initializer_range`` 0.15: ``u phi`` has a standard deviation
    of 0.15 * sqrt(n * 64) = 2.4 at n = 4, the published widths' own."""
    return tiny(n_group=1, topk_group=1, hc_mult=hc_mult,
                initializer_range=0.15, **kw)


def xing_sizes(cfg):
    return dict(sizes_of(cfg), hc_mult=cfg.hc_mult, hc_eps=cfg.hc_eps,
                hc_sinkhorn_iters=cfg.hc_sinkhorn_iters,
                mhc_h_res_clamp_min=cfg.mhc_h_res_clamp_min,
                mhc_h_res_clamp_max=cfg.mhc_h_res_clamp_max)


def engine_of(cfg, params, **inference):
    conf = dict(max_slots=4, max_seq_len=128, block_size=16,
                prefill_chunk=32, paged_kernel=False)
    conf.update(inference)
    return InferenceEngine(cfg, params, config={"inference": conf},
                           mesh=one_device())


# --------------------------------------------------------------------- #
# 1. Served logits against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kernel", [False, True], ids=["onehot", "kernels"])
@pytest.mark.parametrize("hc_mult", [4, 2])
def test_served_logits_match_the_reference(kernel, hc_mult):
    cfg = xing_tiny(hc_mult)
    params = deepseek_v3_init(jax.random.PRNGKey(0), cfg)
    assert float(params["moe"]["hc_attn_alpha"].min()) == 1.0
    assert float(jnp.std(params["moe"]["hc_ffn_b"])) > 0.5
    eng = engine_of(cfg, params, paged_kernel=kernel)
    rng = np.random.default_rng(0)
    first = rng.integers(0, cfg.vocab_size, size=70, dtype=np.int32)
    second = np.concatenate([first[:64], rng.integers(
        0, cfg.vocab_size, size=9, dtype=np.int32)])
    V = cfg.vocab_size
    sizes = xing_sizes(cfg)
    for prompt, cached in ((first, 0), (second, 64)):
        tok, got, info = _serve_one(eng, prompt)
        assert info["cached_tokens"] == cached      # the prefix-hit path
        toks = jnp.asarray(np.concatenate([prompt, [tok]]))
        out = [len(prompt) - 1, len(prompt)]
        want, _ = reference.forward(params, toks, sizes, out_positions=out,
                                    q_block=32)
        np.testing.assert_allclose(got[:, :V], np.asarray(want)[:, :V],
                                   atol=5e-5, rtol=5e-5)
    # ... and the reference's two WRONG models are told apart from it
    # (what the benchmark's comparison must refuse)
    for fault in ("res_identity", "sinkhorn_once"):
        wrong, _ = reference.forward(params, toks, sizes, out_positions=out,
                                     q_block=32, fault=fault)
        assert np.abs(np.asarray(wrong)[:, :V] - got[:, :V]).max() > 0.05, \
            fault
    err = eng.serving.snapshot()["model_counters"]["hc_res_err_max"]
    assert 0.0 <= err < 0.5
    eng.close()


# --------------------------------------------------------------------- #
# 2. The maps
# --------------------------------------------------------------------- #
def _random_maps(seed, T=37, n=4, C=16, scale=1.0, iters=20):
    hc = hyper.HyperConnections(n, iters, 1e-6, (-30.0, 30.0))
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(k[0], (T, 1, n, C), jnp.float32)
    phi = jax.random.normal(k[1], (n * C, hc.columns), jnp.float32) * 0.05
    b = jax.random.normal(k[2], (hc.columns,), jnp.float32)
    alpha = jnp.full((3,), scale, jnp.float32)
    return hc, X, hyper.maps(phi, b * scale, alpha, X, hc)


@pytest.mark.parametrize("n", [4, 2])
def test_h_res_is_doubly_stochastic(n):
    hc, X, m = _random_maps(0, n=n)
    assert m.pre.shape == (n, 37, 1) and m.res.shape == (n, n, 37, 1)
    assert float(m.res.min()) > 0 and float(m.pre.min()) > 0
    assert float(m.post.max()) < 2 and float(m.pre.max()) < 1
    np.testing.assert_allclose(m.res.sum(0), 1.0, atol=1e-4)   # columns
    np.testing.assert_allclose(m.res.sum(1), 1.0, atol=1e-4)   # rows
    assert float(hyper.res_error(m, jnp.ones((37, 1), bool))) < 1e-4
    assert float(hyper.res_error(m, jnp.zeros((37, 1), bool))) == 0.0
    # one iteration normalises the rows and leaves the columns: what the
    # counter is there to read
    _, _, once = _random_maps(0, n=n, iters=1)
    np.testing.assert_allclose(once.res.sum(1), 1.0, atol=1e-5)
    assert float(hyper.res_error(once, jnp.ones((37, 1), bool))) > 1e-2


def test_h_res_stays_doubly_stochastic_at_the_clamp():
    """Logits a thousand times the clamp: ``exp`` sees +-30 and nothing
    overflows.  All at one bound is the uniform matrix; a permutation at
    the upper bound and the rest at the lower is that permutation."""
    hc = hyper.HyperConnections(4, 20, 1e-6, (-30.0, 30.0))
    n, C = 4, 8
    X = jnp.ones((3, 1, n, C), jnp.float32)
    phi = jnp.zeros((n * C, hc.columns), jnp.float32)
    alpha = jnp.ones((3,), jnp.float32)
    perm = np.eye(4, dtype=np.float32)[[2, 0, 3, 1]]
    for logits, want in ((np.full((4, 4), 3e4), np.full((4, 4), 0.25)),
                         (np.full((4, 4), -3e4), np.full((4, 4), 0.25)),
                         ((2 * perm - 1) * 3e4, perm)):
        b = jnp.concatenate([jnp.zeros(2 * n),
                             jnp.asarray(logits, jnp.float32).reshape(-1)])
        m = hyper.maps(phi, b, alpha, X, hc)
        assert bool(jnp.isfinite(m.res).all())
        np.testing.assert_allclose(m.res[..., 0, 0], want, atol=1e-4)
        np.testing.assert_allclose(m.res.sum(0), 1.0, atol=1e-4)
        np.testing.assert_allclose(m.res.sum(1), 1.0, atol=1e-4)


def test_the_mixes_equal_a_literal_per_token_loop():
    hc, X, m = _random_maps(1)
    n = hc.mult
    y = jax.random.normal(jax.random.PRNGKey(9), X.shape[:2] + X.shape[-1:])
    got_in = np.asarray(hyper.mix_in(m, X))
    got_out = np.asarray(hyper.mix_out(m, X, y))
    Xn, yn = np.asarray(X, np.float64), np.asarray(y, np.float64)
    pre, post, res = (np.asarray(a, np.float64) for a in m)
    for t in range(X.shape[0]):
        h = np.zeros(X.shape[-1])
        for j in range(n):
            h += pre[j, t, 0] * Xn[t, 0, j]
        np.testing.assert_allclose(got_in[t, 0], h, atol=1e-5)
        for i in range(n):
            row = post[i, t, 0] * yn[t, 0]
            for j in range(n):
                row = row + res[i, j, t, 0] * Xn[t, 0, j]
            np.testing.assert_allclose(got_out[t, 0, i], row, atol=1e-5)
    # the reference writes the same maps token-major
    sizes = dict(hc_eps=hc.eps, hc_sinkhorn_iters=hc.iters,
                 mhc_h_res_clamp_min=hc.clamp[0],
                 mhc_h_res_clamp_max=hc.clamp[1])
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    phi = jax.random.normal(k[1], (n * 16, hc.columns), jnp.float32) * 0.05
    b = jax.random.normal(k[2], (hc.columns,), jnp.float32)
    rp, rq, rr = reference.residual_maps(X[:, 0], phi, b, jnp.ones(3), sizes)
    np.testing.assert_allclose(rp.T, m.pre[..., 0], atol=1e-6)
    np.testing.assert_allclose(rq.T, m.post[..., 0], atol=1e-6)
    np.testing.assert_allclose(np.moveaxis(np.asarray(rr), 0, -1),
                               m.res[..., 0], atol=1e-6)
    # expansion copies, collapse sums
    x = X[:, :, 0]
    np.testing.assert_array_equal(hyper.expand(x, 3)[:, :, 2], x)
    np.testing.assert_allclose(hyper.collapse(X), X.sum(-2), atol=1e-6)


# --------------------------------------------------------------------- #
# 3. Nothing new enters the cache
# --------------------------------------------------------------------- #
def _cached_rows(eng, slot, n_tokens):
    """The latent rows the cache holds for a slot's first n tokens."""
    pool = np.asarray(eng.cache["latent"])[:, 0, :, 0]     # [L, B, bs/2, 2W]
    rows = np.asarray(la.logical_rows(jnp.asarray(pool)[None],
                                      eng.model_cfg.kv_lora_rank))[0]
    table = np.asarray(eng.block_tables[slot])
    bs = eng.block_size
    return np.stack([rows[:, table[t // bs], t % bs]
                     for t in range(n_tokens)], axis=1)


def test_chunks_and_a_prefix_hit_change_nothing():
    cfg = xing_tiny()
    params = deepseek_v3_init(jax.random.PRNGKey(2), cfg)
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, size=90, dtype=np.int32)

    def run(eng, warm=None):
        if warm is not None:
            _serve_one(eng, warm)
        slot = eng.select_slot(prompt, 4)
        tok, pre = eng.prefill(prompt, slot, return_logits=True,
                               max_new_tokens=4)
        cached = eng.last_admit_info(slot).get("cached_tokens", 0)
        rows = _cached_rows(eng, slot, len(prompt))
        eng.activate_slot(slot, len(prompt), tok)
        _, dec = eng.decode_once(return_logits=True)
        eng.release_slot(slot)
        eng.close()
        return cached, rows, np.stack([pre, dec[slot]])
    one = run(engine_of(cfg, params, prefill_chunk=128))
    three = run(engine_of(cfg, params, prefill_chunk=32))
    hit = run(engine_of(cfg, params, prefill_chunk=32),
              warm=np.concatenate([prompt[:64], prompt[:7]]))
    assert (one[0], three[0], hit[0]) == (0, 0, 64)
    for other in (three, hit):
        np.testing.assert_allclose(other[1], one[1], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(other[2], one[2], atol=5e-5, rtol=5e-5)


def test_a_batch_through_the_scheduler_matches_one_by_one():
    cfg = xing_tiny()
    params = deepseek_v3_init(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in (9, 40, 23, 33)]

    def serve(eng, which):
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=6,
                        arrival_s=0.0) for i in which]
        eng.serve(reqs)
        return {r.rid: list(r.out_tokens) for r in reqs}
    eng = engine_of(cfg, params)
    together = serve(eng, range(4))
    eng.close()
    for i in range(4):
        eng = engine_of(cfg, params)
        assert serve(eng, [i])[i] == together[i], i
        eng.close()


# --------------------------------------------------------------------- #
# 4. Config, parameter tree, scopes, counter
# --------------------------------------------------------------------- #
def test_from_hf_takes_the_five_keys_from_the_published_dict():
    cfg = DeepseekV3Config.from_hf(PUBLISHED)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert (cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max) == (-30, 30)
    assert cfg.hyper == hyper.HyperConnections(4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.hyper.columns == 24
    assert (cfg.n_routed_experts, cfg.n_group, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor) == (64, 1, 4, 2)
    assert (cfg.rope_factor, cfg.rope_theta, cfg.q_lora_rank) == (64, 1e4,
                                                                  768)
    assert cfg.name.endswith("-hc4")
    shapes = jax.eval_shape(lambda k: deepseek_v3_init(
        k, DeepseekV3Config.from_hf(PUBLISHED, held=(0, 64),
                                    num_hidden_layers=3,
                                    first_k_dense_replace=1)),
        jax.random.PRNGKey(0))
    assert shapes["moe"]["hc_attn_phi"].shape == (2, 4 * 3584, 24)
    assert shapes["dense"]["hc_ffn_b"].shape == (1, 24)
    assert shapes["moe"]["hc_ffn_alpha"].dtype == jnp.float32
    with pytest.raises(ValueError):
        DeepseekV3Config(hc_mult=1)
    # the published deepseek_v3 dict has none of them: one stream
    plain = {k: v for k, v in PUBLISHED.items()
             if not k.startswith(("hc_", "mhc_"))}
    assert DeepseekV3Config.from_hf(plain).hyper is None


def _program_op_names(eng):
    G, J = eng.dp, eng.cache_spec.max_blocks_per_slot
    key, temp = eng._next_key(), np.float32(0.0)
    pool = eng.cache["latent"]
    return {
        "decode": _op_names(eng._decode_fn, eng._params, pool,
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, pool,
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, J), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(1), key,
            temp)}


def test_without_the_keys_the_tree_and_the_programs_are_todays():
    with_hc = xing_tiny()
    plain = tiny(n_group=1, topk_group=1, initializer_range=0.15)
    assert plain.hyper is None and plain.name + "-hc4" == with_hc.name
    a = deepseek_v3_init(jax.random.PRNGKey(4), plain)
    b = deepseek_v3_init(jax.random.PRNGKey(4), with_hc)
    for group in ("dense", "moe"):
        assert not [k for k in a[group] if k.startswith("hc_")]
        assert sorted(set(b[group]) - set(a[group])) == sorted(
            f"hc_{sub}_{leaf}" for sub in ("attn", "ffn")
            for leaf in ("phi", "b", "alpha"))
        b[group] = {k: v for k, v in b[group].items() if k in a[group]}
    # every leaf the two share is the same array: the maps draw from keys
    # of their own
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    eng = engine_of(plain, a)
    assert eng.served.counter_names == (
        "moe_held_pairs", "moe_held_max", "moe_held_empty", "moe_rows")
    assert eng._no_fetch.shape == (eng.max_slots + 4,)
    for program, names in _program_op_names(eng).items():
        assert not [n for n in names if "/hc" in n], program
    eng.close()


@pytest.fixture(scope="module")
def hc_engine():
    cfg = xing_tiny()
    eng = engine_of(cfg, deepseek_v3_init(jax.random.PRNGKey(5), cfg),
                    paged_kernel=True)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def hc_op_names(hc_engine):
    return _program_op_names(hc_engine)


@pytest.mark.parametrize("scope", [
    "embed/hc_expand", "attn/hc/hc_maps", "attn/hc/hc_pre",
    "attn/hc/hc_post", "mlp/hc/hc_maps", "mlp/hc/hc_pre", "mlp/hc/hc_post",
    "moe/hc/hc_maps", "moe/hc/hc_pre", "moe/hc/hc_post",
    "lm_head/hc_collapse", "attn/latent_proj", "attn/attend", "moe/experts"])
def test_the_programs_carry_the_hc_scopes_inside_their_sublayers(hc_op_names,
                                                                 scope):
    for program in ("decode", "prefill"):
        assert any(f"/{scope}" in n for n in hc_op_names[program]), program


def test_the_counter_rides_the_fetch_and_the_readers_know_the_names(
        hc_engine):
    from deepspeed_tpu.monitor.xplane_reader import (SCOPES, SPAN_ARGS,
                                                     scope_of)
    eng = hc_engine
    assert eng.served.counter_names[-1] == "hc_res_err_max"
    assert eng._no_fetch.shape == (eng.max_slots + 5,)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, 250, size=11 + 9 * i,
                                               dtype=np.int32),
                    max_new_tokens=5, arrival_s=0.0) for i in range(3)]
    eng.reset_serving_stats()
    report = eng.serve(reqs)
    assert report["completed"] == 3
    err = report["model_counters"]["hc_res_err_max"]
    assert 0.0 < err < 0.5                  # a float, not its bits
    # the counter's bits decode to the float the maps give
    rows = np.zeros((2, 5), np.int64)
    rows[:, 4] = np.asarray([1e-3, 2e-5], np.float32).view(np.int32)
    rows[:, 3] = 1
    assert eng.served.counter_args(rows)["hc_res_err_max"] == \
        pytest.approx(1e-3)
    assert {"hc", "hc_maps", "hc_pre", "hc_post", "hc_expand",
            "hc_collapse"} <= set(SCOPES)
    assert scope_of("jit(decode_step)/while/body/moe/hc/hc_maps/exp")[0] \
        == ("moe", "hc", "hc_maps")
    assert "hc_res_err_max" in SPAN_ARGS["decode"]
    assert "hc_res_err_max" in SPAN_ARGS["prefill"]

"""The decode loop runs one iteration ahead of its token fetch
(``InferenceEngine.decode_once`` with ``continuing``, driven by
``ContinuousBatchingScheduler.serve``): iteration n+1 is dispatched, fed
by n's tokens where they lie on the device, before the host reads n's
tokens.

What has to hold, for every served family (GPT-2's paged K/V, the latent
family, the retention family's state a stream, the two classes of window
and full-attention layers): each request gets, token
for token, what a loop of SYNCHRONOUS ``decode_once()`` calls gives it on
a second engine with the same weights — through admissions into freed
slots, an EOS found one iteration late (its extra row dropped and
counted), a serve cut with an iteration in flight, and a dp = 2 mesh —
and ``serve()`` never returns with anything in flight or held.

The same engines (one a family) also show that the engine asks its cache
manager no kind: an allocator out of the one factory, a miss and a prefix
hit, and a prefill that fails with its own cause, whatever the family.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, kv_cache
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.parallel.topology import build_mesh


# ------------------------------------------------------------------ #
# The four families at toy sizes
# ------------------------------------------------------------------ #
def _gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_init
    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32)
    return cfg, gpt2_init(jax.random.PRNGKey(0), cfg), cfg.vocab_size, {
        "max_slots": 4, "max_seq_len": 64, "prefill_chunk": 8,
        "block_size": 16}


def _latent():
    from deepspeed_tpu.models.deepseek_v3 import deepseek_v3_init
    from test_latent_serving import tiny
    cfg = tiny(held=(4, 8))
    return cfg, deepseek_v3_init(jax.random.PRNGKey(0), cfg), 250, {
        "max_slots": 4, "max_seq_len": 64, "prefill_chunk": 8,
        "block_size": 16}


def _retention():
    from deepspeed_tpu.models.brumby import BrumbyConfig, brumby_init
    cfg = BrumbyConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16,
                       max_position_embeddings=128, dtype=jnp.float32)
    return cfg, brumby_init(jax.random.PRNGKey(0), cfg), 128, {
        "max_slots": 4, "max_seq_len": 128, "prefill_chunk": 16,
        "block_size": 8, "num_blocks": 8}


def _two_class():
    from deepspeed_tpu.models.afmoe import afmoe_init
    from test_afmoe_serving import tiny
    cfg = tiny()
    return cfg, afmoe_init(jax.random.PRNGKey(0), cfg), 128, {
        "max_slots": 4, "max_seq_len": 128, "prefill_chunk": 8,
        "block_size": 4, "num_blocks": {"full": 64, "window": 40}}


FAMILIES = {"gpt2": _gpt2, "latent": _latent, "retention": _retention,
            "two_class": _two_class}
_BUILT = {}


def _engines(family, dp):
    """(engine under test, reference engine, vocabulary): built once a
    family and mesh — every case leaves them as it found them, which is
    part of what is tested."""
    if (family, dp) not in _BUILT:
        cfg, params, vocab, inference = FAMILIES[family]()
        mesh = build_mesh(devices=jax.devices()[:dp])
        _BUILT[family, dp] = tuple(
            InferenceEngine(cfg, params, config={"inference": inference},
                            mesh=mesh) for _ in range(2)) + (vocab,)
    return _BUILT[family, dp]


def _requests(vocab, n=7, new=(4, 13), gap_s=0.0, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival_s=i * gap_s,
                    prompt=rng.integers(0, vocab, size=5 + (3 * i) % 9,
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(*new)))
            for i in range(n)]


def _synchronous(ref, reqs, eos=None):
    """{rid: tokens} of a loop of bare ``decode_once()`` calls."""
    out = {}
    for r in reqs:
        slot = ref.select_slot(r.prompt, r.max_new_tokens)
        tok, _ = ref.prefill(r.prompt, slot,
                             max_new_tokens=r.max_new_tokens)
        ref.activate_slot(slot, len(r.prompt), tok)
        toks = [tok]
        while len(toks) < r.max_new_tokens and toks[-1] != eos:
            sampled, _ = ref.decode_once()
            toks.append(int(sampled[slot]))
        ref.release_slot(slot)
        out[r.rid] = toks
    return out


def _serve(eng, reqs, **kw):
    eng.reset_serving_stats()          # the report is this serve's own
    return eng.serve(reqs, **kw)


def _free(eng):
    return [eng.allocator.available(g) for g in range(eng.dp)]


def _left_clean(eng, free0):
    assert eng._inflight is None, "serve() returned with an iteration in flight"
    assert not eng.active.any() and not eng._held
    assert (eng.block_tables < 0).all() and not eng.lengths.any()
    assert eng.allocator.blocks_in_use() == 0 and _free(eng) == free0


# ------------------------------------------------------------------ #
# One parametrised test: family x what happens to the loop
# ------------------------------------------------------------------ #
def _staggered(eng, ref, vocab):
    """Arrivals spread over the run, more requests than slots: streams
    are admitted mid-flight into slots others freed."""
    reqs = _requests(vocab, n=9, gap_s=0.003)
    want = _synchronous(ref, reqs)
    free0 = _free(eng)
    report = _serve(eng, reqs)
    assert report["completed"] == len(reqs) and report["unfinished"] == 0
    for r in reqs:
        assert r.out_tokens == want[r.rid], r.rid
    assert report["iterations"] > 9 and report["lookahead_share"] > 0.5
    assert report["lookahead_dropped_rows"] == 0    # every end was known
    _left_clean(eng, free0)


def _eos(eng, ref, vocab):
    """A stream that emits the EOS mid-reply is found out one iteration
    late: the row computed for it meanwhile is dropped and counted, and
    the user sees what the synchronous loop gives."""
    reqs = _requests(vocab, new=(8, 14))
    plain = _synchronous(ref, reqs)
    # a token some stream first emits inside its reply: out of a decode
    # iteration, and not as the last one its length allows
    eos = next(t for r in reqs for i, t in enumerate(plain[r.rid])
               if 1 <= i < r.max_new_tokens - 1
               and t not in plain[r.rid][:i])
    want = _synchronous(ref, reqs, eos=eos)
    # dropped: the EOS came out of a decode iteration (not the prefill)
    # and was not the reply's last token by length anyway
    late = [r.rid for r in reqs if want[r.rid][-1] == eos
            and 1 < len(want[r.rid]) < r.max_new_tokens]
    assert late
    free0 = _free(eng)
    report = _serve(eng, reqs, eos_token=eos)
    for r in reqs:
        assert r.out_tokens == want[r.rid], r.rid
        assert len(r.out_tokens) <= r.max_new_tokens
    assert report["lookahead_dropped_rows"] == len(late)
    assert report["completed"] == len(reqs)
    _left_clean(eng, free0)


def _cut(eng, ref, vocab):
    """``max_wall_s`` cuts the serve with an iteration in flight: it is
    discarded, every slot comes back, and the same engine serves on."""
    long = _requests(vocab, n=4, new=(40, 48))
    for r in long:
        r.prompt = r.prompt[:6]
    want = _synchronous(ref, long)
    free0 = _free(eng)
    in_flight = []
    discard = eng.decode_discard

    def spy():
        in_flight.append(eng._inflight is not None)
        discard()
    eng.decode_discard = spy
    try:
        # (the engine is warm: a cold compile would outlast any wall)
        _serve(eng, _requests(vocab, n=2))
        report = _serve(eng, long, max_wall_s=0.03)
    finally:
        del eng.decode_discard
    assert in_flight == [True] and report["completed"] == 0
    for r in long:                     # what they got is what they should
        assert 0 < len(r.out_tokens) < r.max_new_tokens
        assert r.out_tokens == want[r.rid][:len(r.out_tokens)]
    _left_clean(eng, free0)
    again = _requests(vocab, seed=1)
    want = _synchronous(ref, again)
    assert _serve(eng, again)["completed"] == len(again)
    for r in again:
        assert r.out_tokens == want[r.rid], r.rid
    _left_clean(eng, free0)


CASES = {"staggered": (_staggered, 1), "eos": (_eos, 1), "cut": (_cut, 1),
         "dp2": (_staggered, 2)}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_serve_ahead_emits_the_synchronous_loops_tokens(family, case):
    run, dp = CASES[case]
    run(*_engines(family, dp))


# ------------------------------------------------------------------ #
# One compiled form; the bare call
# ------------------------------------------------------------------ #
def test_no_compile_after_the_first_two_iterations():
    """The first dispatch of a serve takes zeros for the previous fetch
    and an all-fresh mask, the later ones an execution's own output: one
    compiled form, so nothing compiles once two iterations have run."""
    import jax.monitoring
    cfg, params, vocab, inference = _gpt2()
    eng = InferenceEngine(cfg, params, config={"inference": inference},
                          mesh=build_mesh(devices=jax.devices()[:1]))
    log = []

    def listener(name, *_, **__):
        if "backend_compile" in name:
            log.append("compile")
    jax.monitoring.register_event_duration_secs_listener(listener)
    once = eng.decode_once

    def counted(*a, **kw):
        out = once(*a, **kw)
        log.append("decode")
        return out
    eng.decode_once = counted
    try:
        report = eng.serve(_requests(vocab, n=9, gap_s=0.002))
    finally:
        del eng.decode_once
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(listener)
    assert report["completed"] == 9 and log.count("compile") >= 2
    # (the third call returns the second iteration's tokens)
    third = [i for i, what in enumerate(log) if what == "decode"][2]
    assert "compile" not in log[third:], log
    assert eng._decode_fn._cache_size() == 1
    # a second serve and a bare call run that same form
    eng.serve(_requests(vocab, n=3, seed=2))
    slot = eng.select_slot(np.arange(5, dtype=np.int32), 4)
    tok, _ = eng.prefill(np.arange(5, dtype=np.int32), slot,
                         max_new_tokens=4)
    eng.activate_slot(slot, 5, tok)
    eng.decode_once()
    eng.release_slot(slot)
    assert eng._decode_fn._cache_size() == 1


def test_the_bare_call_is_synchronous():
    """``decode_once()`` as before: its own iteration's tokens and
    logits, nothing left in flight; and it refuses to run over an
    iteration that is."""
    eng, _, vocab = _engines("gpt2", 1)
    prompt = np.arange(3, 12, dtype=np.int32)
    slot = eng.select_slot(prompt, 8)
    tok, _ = eng.prefill(prompt, slot, max_new_tokens=8)
    eng.activate_slot(slot, len(prompt), tok)
    try:
        length = eng.context_len(slot)
        for i in range(3):
            sampled, logits = eng.decode_once(return_logits=True)
            assert eng._inflight is None
            assert sampled[slot] == int(np.argmax(logits[slot]))
            assert eng.last_tokens[slot] == sampled[slot]
            assert eng.context_len(slot) == length + i + 1
        # ahead: the first call returns nothing, the next this one's
        assert eng.decode_once(continuing=[slot]) == (None, None)
        assert eng.context_len(slot) == length + 4     # at the dispatch
        with pytest.raises(RuntimeError, match="in flight"):
            eng.decode_once()
        with pytest.raises(ValueError):
            eng.decode_once(continuing=[slot], return_logits=True)
        ahead, took = eng.decode_once(continuing=())
        assert took[slot] and took.sum() == 1 and eng._inflight is None
        # ... which is what the synchronous call would have sampled
        assert eng.last_tokens[slot] == ahead[slot]
        again, logits = eng.decode_once(return_logits=True)
        assert again[slot] == int(np.argmax(logits[slot]))
        with pytest.raises(ValueError, match="not active"):
            eng.decode_once(continuing=[(slot + 1) % eng.max_slots])
    finally:
        eng.decode_discard()
        eng.release_slot(slot)


# ------------------------------------------------------------------ #
# The engine asks its cache manager no kind
# ------------------------------------------------------------------ #
def _document(eng, vocab, seed):
    """A prompt long enough to be worth caching whatever the family keeps
    (a state's snapshot wants a page's worth of tokens), with room left
    for a question and a reply."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=min(100, eng.max_len - 24),
                        dtype=np.int32)


def _through(eng, prompt):
    """(first token, its logits, the admission's detail) of ``prompt``
    through the engine's one admission path, the slot given back."""
    slot = eng.select_slot(prompt, 4)
    tok, logits = eng.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=4)
    info = dict(eng.last_admit_info(slot))
    eng.release_slot(slot)
    return tok, logits, info


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_engine_asks_no_cache_kind(family):
    """Pages, a ring beside pages, a state: the engine builds each from
    the one factory and admits a miss and a prefix hit through the same
    calls; the hit's logits are a cold engine's."""
    eng, ref, vocab = _engines(family, 1)
    made = kv_cache.allocator_for(eng.cache_specs, eng.spec_k)
    assert type(eng.allocator) is type(made)
    assert [type(a) for a in getattr(eng.allocator, "classes", [])] \
        == [type(a) for a in getattr(made, "classes", [])]
    free0 = _free(eng)
    doc = _document(eng, vocab, seed=11)
    question = np.concatenate([doc, _document(eng, vocab, seed=12)[:5]])
    assert eng.prefix_match_tokens(question) == 0
    _, _, info = _through(eng, doc)
    assert info["cached_tokens"] == 0 and info["chunks"] > 1
    cached = eng.prefix_match_tokens(question)
    assert 0 < cached <= len(doc)
    tok, logits, info = _through(eng, question)
    assert info["cached_tokens"] == cached
    cold_tok, cold, info = _through(ref, question)
    assert info["cached_tokens"] == 0 and tok == cold_tok
    np.testing.assert_allclose(logits, cold, atol=2e-4)
    _left_clean(eng, free0)
    _left_clean(ref, _free(ref))


def test_the_engines_source_names_no_cache_kind():
    import inspect
    source = inspect.getsource(engine_mod)
    for word in ("per_stream", "BlockAllocator", "BoundedBlockAllocator",
                 "StateAllocator", "ClassAllocators"):
        assert word not in source, word


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_failed_prefill_reraises_its_cause(family):
    """A chunk program that raises surfaces as what it raised, whatever
    the cache manager (every one takes ``abandon_snapshot``); a page set
    aside for a snapshot that was never taken is back in its free list;
    and the engine serves on."""
    eng, ref, vocab = _engines(family, 1)
    free0 = _free(eng)
    doc = _document(eng, vocab, seed=21)
    real, calls = eng._prefill_fn, []

    def failing(*args):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("a bad chunk")
        return real(*args)
    eng._prefill_fn = failing
    slot = eng.select_slot(doc, 4)
    try:
        with pytest.raises(RuntimeError, match="a bad chunk"):
            eng.prefill(doc, slot, max_new_tokens=4)
    finally:
        eng._prefill_fn = real
        eng.release_slot(slot)
    _left_clean(eng, free0)
    # (a page pool's index keeps the failed prompt's blocks, as it always
    # has: ROADMAP; an unrelated prompt is served as a cold engine would)
    other = _document(eng, vocab, seed=22)
    tok, logits, _ = _through(eng, other)
    cold_tok, cold, _ = _through(ref, other)
    assert tok == cold_tok
    np.testing.assert_allclose(logits, cold, atol=2e-4)
    _left_clean(eng, free0)

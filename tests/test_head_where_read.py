"""The head and the sampler run only where their result is read (ISSUE 55).

``prefill_step`` branches on an operand the host sets (``read``: does this
dispatch END some group's prompt): the taken branch is the model's head and
``sample_tokens`` as every chunk program ran them before, the other returns
zeros; ``sample_tokens`` branches on the traced temperature.

What is held to what, on the CPU with a tiny engine of EVERY served family,
kernels off (here) and on (interpret mode: ``test_head_where_read_kernels.py``):
(a) a prompt of three chunks and one of one chunk give the first token, the
    logits and (kernels off) two decode iterations of an engine that
    computes the head in every chunk program and samples by a select (the
    parent's path: the family's ``prefill_chunk`` + ``head`` called
    directly), bit for bit;
(b) a chunk program that ends no prompt returns zero tokens and logits with
    the model's counters still on its fetch array, and in the lowered text
    the head's product sits inside a ``case`` region and nowhere else;
(c) ``sample_tokens`` at temperature 0 is ``argmax`` and at 0.7 the draw
    ``jax.random.categorical(key, logits / 0.7)`` gives, and both programs
    compile once across the two temperatures;
(d) the ``prefill_chunk`` spans' ``head`` args add up to the chunk programs
    dispatched: one with a head a prompt, the rest without;
(e) ``dp`` = 2 and 4 on host devices: a dispatch in which one group ends
    its prompt and another does not serves both what one device serves.
"""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from deepspeed_tpu.inference import engine as engine_mod        # noqa: E402
from deepspeed_tpu.inference import served as served_mod        # noqa: E402
from deepspeed_tpu.inference.scheduler import Request           # noqa: E402

import decode_step_hlo                                          # noqa: E402

CHUNK = decode_step_hlo.CHUNK
# Every served family: GPT-2, the latent family with a held share and with
# several residual streams, retention, two classes of pages, a router ahead
# of its attention, pages beside a conv state, a state-space mixer beside
# attention, a delta-rule state beside a latent class — the fixtures
# ``tests/test_program_text.py`` holds to their programs' text.
FAMILIES = decode_step_hlo.FAMILIES
_engine = decode_step_hlo.engine


def _prompt(n, vocab, seed):
    return np.random.default_rng(seed).integers(0, vocab, size=n,
                                                dtype=np.int32)


# --------------------------------------------------------------------- #
# The parent's path: the head in every chunk program, a select for a cond
# --------------------------------------------------------------------- #
def _select_sample(logits, key, temperature):
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    sampled = jax.random.categorical(key, logits / t, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _head_in_every_program(read, head, h, key, temperature):
    logits = head(h)
    return _select_sample(logits, key, temperature), logits


def _span_heads(eng):
    """The ``head`` arg of every ``prefill_chunk`` span from here on."""
    real, heads = eng.telemetry.span, []

    def span(name, **args):
        if name == "prefill_chunk":
            heads.append(args["head"])
        return real(name, **args)
    eng.telemetry.span = span
    return heads


def _watched(eng):
    """Every ``prefill_step`` dispatch from here on: (its ``read`` operand,
    its fetch array, its logits)."""
    real, seen = eng._prefill_fn, []
    n = len(eng._cache_sh)

    def watched(*args):
        out = real(*args)
        seen.append((int(args[-3]), np.asarray(out[n]), np.asarray(out[n + 1])))
        return out
    eng._prefill_fn = watched
    return seen


def _serve_two(eng, vocab, temperature=0.0, seeds=(1, 2), decode=2):
    """A prompt of three chunks, then one of one chunk, ``decode``
    iterations each: [(first token, prefill logits, decoded tokens, last
    decode logits)]."""
    out = []
    for seed, n in zip(seeds, (2 * CHUNK + 3, CHUNK - 3)):
        prompt = _prompt(n, vocab, seed)
        slot = eng.select_slot(prompt, 4)
        tok, logits = eng.prefill(prompt, slot, temperature=temperature,
                                  return_logits=True, max_new_tokens=4)
        assert eng.last_admit_info(slot)["chunks"] == -(-n // CHUNK)
        eng.activate_slot(slot, n, tok)
        toks, step_logits = [], logits[None].repeat(eng.max_slots, 0)
        for _ in range(decode):
            sampled, step_logits = eng.decode_once(temperature,
                                                   return_logits=True)
            toks.append(int(sampled[slot]))
        out.append((tok, logits, toks, np.asarray(step_logits)[slot]))
        eng.release_slot(slot)
    return out


def _vocab(eng):
    return int(getattr(eng.model_cfg, "vocab_size"))


def _in_a_case(text):
    """(inside a ``stablehlo.case`` region?, line) of a lowered module."""
    depth, cases = 0, []
    for line in text.splitlines():
        inside = bool(cases)
        if '"stablehlo.case"' in line:
            cases.append(depth)
        depth += line.count("{") - line.count("}")
        while cases and depth <= cases[-1]:
            cases.pop()
        yield inside, line


def _prefill_text(eng):
    G, J = eng.dp, eng.allocator.table_width
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)   # noqa
    return eng._build_prefill_step().lower(
        eng._params, *eng._pools(), i32(G, CHUNK), i32(G, J), i32(G), i32(G),
        i32(G), *[i32(G) for _ in eng._no_freeze()], i32(),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.float32)).as_text()


def served_what_the_head_in_every_program_served(family, kernel,
                                                 monkeypatch):
    """(a) and (b) for one family; with the kernels on (interpret mode) the
    cases are ``test_head_where_read_kernels.py``'s, a file of their own so
    that the suite's workers share them."""
    with monkeypatch.context() as parent:
        parent.setattr(engine_mod, "head_and_sample", _head_in_every_program)
        parent.setattr(engine_mod, "sample_tokens", _select_sample)
        # (the decode program's sampler is the same code with kernels on
        # and off: its iterations run where they compile fastest)
        ref = _engine(family, kernel)
        ref_seen = _watched(ref)
        want = _serve_two(ref, _vocab(ref), decode=0 if kernel else 2)
        ref.close()
    eng = _engine(family, kernel)
    seen, heads = _watched(eng), _span_heads(eng)
    got = _serve_two(eng, _vocab(eng), decode=0 if kernel else 2)
    for (tok, logits, toks, last), (wtok, wlogits, wtoks, wlast) \
            in zip(got, want):
        assert tok == wtok and toks == wtoks
        np.testing.assert_array_equal(logits, wlogits)
        np.testing.assert_array_equal(last, wlast)
    # (b) three chunks + one: only the two that end a prompt have a head;
    # the others hand back zeros, the model's counters riding all the same.
    assert [read for read, _, _ in seen] == [0, 0, 1, 1]
    assert [read for read, _, _ in ref_seen] == [0, 0, 1, 1]
    n_ctr = len(eng.served.counter_names)
    for (read, fetch, logits), (_, wfetch, wlogits) in zip(seen, ref_seen):
        assert fetch.shape == (eng.dp + n_ctr,)
        if read:
            np.testing.assert_array_equal(fetch, wfetch)
            np.testing.assert_array_equal(logits, wlogits)
        else:
            assert not logits.any() and not fetch[:eng.dp].any()
            assert wlogits.any()         # (the parent computed them)
            np.testing.assert_array_equal(fetch[eng.dp:], wfetch[eng.dp:])
    assert heads == [0, 0, 1, 1]
    if not kernel:
        # The head's product — the one contraction of a row with a ``[H,
        # V]`` weight into ``[G, V]`` logits — is inside a ``case`` region,
        # and only there.
        V = got[0][1].shape[-1]
        product = re.compile(
            rf", tensor<\d+x{V}xf32>\) -> tensor<{eng.dp}x{V}xf32>")
        products = [inside for inside, line in _in_a_case(_prefill_text(eng))
                    if "stablehlo.dot_general" in line
                    and product.search(line)]
        assert products and all(products), products
    eng.close()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_prompt_is_served_what_the_head_in_every_program_served(
        family, monkeypatch):
    served_what_the_head_in_every_program_served(family, False, monkeypatch)


# --------------------------------------------------------------------- #
# (c) The sampler's branch
# --------------------------------------------------------------------- #
def test_greedy_is_argmax_and_a_draw_is_categoricals():
    logits = jax.random.normal(jax.random.PRNGKey(3), (5, 3, 257),
                               jnp.float32) * 3.0
    key = jax.random.PRNGKey(11)
    sample = jax.jit(served_mod.sample_tokens)
    greedy = sample(logits, key, jnp.float32(0.0))
    assert greedy.dtype == jnp.int32
    np.testing.assert_array_equal(greedy, jnp.argmax(logits, axis=-1))
    drawn = sample(logits, key, jnp.float32(0.7))
    np.testing.assert_array_equal(
        drawn, jax.random.categorical(key, logits / jnp.float32(0.7)))
    np.testing.assert_array_equal(
        drawn, _select_sample(logits, key, jnp.float32(0.7)))
    assert (np.asarray(drawn) != np.asarray(greedy)).any()
    assert sample._cache_size() == 1
    # the draw is under the branch: a greedy step holds no noise outside it
    text = jax.jit(served_mod.sample_tokens).lower(
        logits, key, jnp.float32(0.0)).as_text()
    calls = [inside for inside, line in _in_a_case(text)
             if "call @_gumbel" in line or "call @argmax" in line]
    assert len(calls) >= 2 and all(calls)
    # speculation's acceptance samples through the same function
    tokens = jnp.zeros((5, 3), jnp.int32)
    out = served_mod.spec_accept(logits, tokens, key, jnp.float32(0.0))
    np.testing.assert_array_equal(out[:, 1:], greedy)


def test_one_program_across_temperatures():
    eng = _engine("gpt2", False)
    greedy = _serve_two(eng, _vocab(eng), temperature=0.0)
    drawn = _serve_two(eng, _vocab(eng), temperature=0.7, seeds=(3, 4))
    assert eng._decode_fn._cache_size() == 1
    assert eng._prefill_fn._cache_size() == 1
    for tok, logits, toks, _ in greedy:
        assert tok == int(np.argmax(logits))
    assert any(tok != int(np.argmax(logits)) or toks[0] != toks[1]
               for tok, logits, toks, _ in drawn)
    eng.close()


# --------------------------------------------------------------------- #
# (d) The span's arg and the snapshot's counts
# --------------------------------------------------------------------- #
def test_the_head_counts_add_up_to_the_chunk_programs(tmp_path):
    trace_path = str(tmp_path / "host.trace.json")
    eng = _engine("gpt2", False, telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "h",
        "report_steps": 10 ** 6, "trace_path": trace_path})
    lengths = [5, 8, 9, 20, 30]
    rng = np.random.default_rng(5)
    report = eng.serve([
        Request(rid=i, arrival_s=0.0, max_new_tokens=3,
                prompt=rng.integers(0, _vocab(eng), size=n, dtype=np.int32))
        for i, n in enumerate(lengths)])
    assert report["completed"] == len(lengths)
    chunks = [-(-n // CHUNK) for n in lengths]
    assert sum(report["prefill_width_dispatches"].values()) == sum(chunks)
    eng.close()
    events = [e for e in json.load(open(trace_path))
              if e.get("name") in ("prefill", "prefill_chunk")]
    heads = [e["args"]["head"] for e in events
             if e["name"] == "prefill_chunk"]
    assert (heads.count(1), heads.count(0)) == (
        len(lengths), sum(chunks) - len(lengths))
    prefills = [e for e in events if e["name"] == "prefill"]
    assert len(prefills) == len(lengths)
    for pf in prefills:
        heads = [c["args"]["head"] for c in events
                 if c["name"] == "prefill_chunk"
                 and pf["ts"] <= c["ts"] < pf["ts"] + pf["dur"]]
        assert heads == [0] * (pf["args"]["chunks"] - 1) + [1]
        assert pf["args"]["chunks"] == -(-pf["args"]["prompt_tokens"] // CHUNK)


# --------------------------------------------------------------------- #
# (e) Several groups: one ends its prompt in a dispatch, another does not
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("family,dp", [
    ("gpt2", 2), ("gpt2", 4), ("latent_share", 2), ("retention", 2),
    ("afmoe", 2)])
def test_groups_that_end_in_different_dispatches(family, dp):
    one = _engine(family, False)
    many = _engine(family, False, dp=dp)
    vocab = _vocab(one)
    Sg = many.max_slots // dp
    # group 0: three chunks; group 1: one; (dp 4) group 3: two
    lengths = {0: 2 * CHUNK + 3, 1: CHUNK - 3, 3: CHUNK + 1}
    admissions = [(g * Sg, _prompt(n, vocab, 30 + g), 4)
                  for g, n in lengths.items() if g < dp]
    seen, heads = _watched(many), _span_heads(many)
    got = many.prefill_many(admissions, return_logits=True)
    # dispatch 0 ends group 1's prompt, 1 (dp 4) group 3's, 2 group 0's
    assert [read for read, _, _ in seen] == [1, int(dp == 4), 1]
    assert heads == [1, int(dp == 4), 1]
    for (slot, prompt, _), (tok, logits) in zip(admissions, got):
        alone = one.select_slot(prompt, 4)
        wtok, wlogits = one.prefill(prompt, alone, return_logits=True,
                                    max_new_tokens=4)
        one.release_slot(alone)
        assert tok == wtok
        np.testing.assert_allclose(logits, wlogits, atol=2e-5)
    one.close()
    many.close()

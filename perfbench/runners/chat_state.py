"""kind: chat_state -- a chat endpoint at peak: short prompts behind a handful
of system prompts, short replies, far more requests than slots, through
``InferenceEngine.serve``, for a configuration of the ``falcon_h1`` family:
EVERY layer runs a Mamba-2 state-space mixer, which keeps a float32 state a
stream, and grouped-query attention, which keeps K/V pages a token — two
KINDS of cache of two dtypes in one layer, one manager, one prefix rule.

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile the prefill chunk and the decode
step; EVERY SYSTEM PROMPT SERVED ONCE (1 new token) through ``engine.serve``
so that its blocks and its state snapshot AT its end sit in the prefix cache
(a hit needs both kinds); the float32 reference comparison and its controls
(its resumed prompt builds the page copy); ``reset_serving_stats()``.
Window: ``backlog`` requests due at 0 and an open loop over ``[0,
--seconds)`` at the traffic file's fixed rate, above what the system
sustains, cut by the scheduler at the window's end (``lib/reason_traffic
.py``): decode of ``max_slots`` streams over short contexts, where a layer's
state traffic outweighs its K/V read, plus ~25 admissions a second through
the chunked scan.  After the window: every emitted token of two requests
served inside the full batch against the reference.

``correct`` (decided on the chip at the published widths, from what the
timed path produced; logits and pages, not tokens), every part of it:
1. logits through BOTH kinds of cache against the reference's full forward
   (``lib/falcon_h1_reference.py``: the recurrence token by token) in three
   groups: ``short``: the prefill and first decode of ``N_SHORT`` unshared
   prompts of ``SHORT_LEN`` tokens; ``long``: one unshared prompt of
   ``LONG_LEN`` tokens (three chunk programs, the state carried from one to
   the next), its prefill and first decode; ``resumed``: a prompt admitted
   on a cached system prompt (it must have resumed at the system prompt's
   end in BOTH classes: pages by reference + the snapshot copied), its
   prefill and ``RESUMED_STEPS`` decode iterations, against the reference
   over the whole sequence from position 0.  Every position within
   ``LOGIT_ATOL`` (``logits_agree``: the model is dense, no routing decision
   can flip);
2. the state PAGES of those streams (after prefill; the resumed one after
   its last checked iteration too) against the reference's ``S_t`` and
   filter rows, a layer each, by relative error (Frobenius), within
   ``PAGE_RTOL`` (``pages_agree``): what holds a PATH (the chunks' carried
   state, the snapshot's copy);
3. the state's OWN arithmetic, apart from everything upstream of it, in two
   links over LAYER 0 (whose inputs depend on nothing a state or an
   attention does).  THE STEPS: the x, B and dt that the program's
   embedding, norm, projection and filter give for a run of tokens against
   ``reference.first_layer_steps`` (float32, from the weights alone,
   independent of the program), a relative error each, within
   ``STEPS_RTOL``: what the bf16 activations the configuration states cost,
   and no more.  THE STATE: the page the program holds after the run
   against the page it held before it, carried over the run by
   ``reference.carry_state`` (float32, token by token) on those steps:
   (a) DECODE, the resumed stream's page after ``CARRIED_STEPS`` more
   iterations (that many in-place updates of the kernel), within
   ``STATE_RTOL``; (b) PREFILL, the ``long`` stream's page after EVERY
   chunk program but the first against its page after the program before
   (the chunked scan over up to ``prefill_chunk`` rows from a carried
   state, at the precision the configuration states for its products),
   within ``CHUNK_RTOL``.  The links are two because ONE comparison against
   float32 steps cannot part a float32 state from a bfloat16 one: the
   steps' own rounding reads 0.5% of a page, as much as a bfloat16 state
   adds (readings under the limits below);
4. the comparison can fail, shown every run on the same positions, the
   reference's wrong models read against the true reference under the same
   rules: the state carried in BFLOAT16 must fail 3 (a) and 3 (b); the
   reference's steps held in 8 bits (e4m3) must fail 3's first link; the
   state-space branch dropped, ``D`` = 0 and ``ssm_multipliers`` all 1 must
   each fail 1 on the ``short`` prompts; the state ZEROED at the snapshot's
   boundary must fail 1 and 2 on the ``resumed`` stream;
5. every emitted token of two requests served inside the full batch (the
   latest-started finished one behind a system prompt and the latest-started
   finished one that shares nothing) within ``TOKEN_GAP`` of the reference's
   largest logit in its teacher-forced forward;
6. every system prompt still resumable at its end when the window opens, no
   request over its length, zero compiles in the window, some output.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import falcon_h1 as falcon_h1_model  # fails at once
#          on a program that has no such family: nothing has run yet
from perfbench.lib import falcon_h1_reference as reference
from perfbench.lib import reason_traffic, traffic as traffic_lib, xplane
from perfbench.runners import _common, serve as serve_runner
from perfbench.runners.mixed_docqa import _class_state, measure

# Served logits (bf16 weights, activations, K/V pools and filter rows; fp32
# state, dt, decay, norms, softmax and accumulation) against the float32
# reference on the same bf16 weights upcast.  Logits of the seeded model have
# a standard deviation of about 1 (``falcon_h1_init``: unit-variance logits
# under ``lm_head_multiplier``); the model is dense, so no decision can flip
# and one limit holds every position.  Read on the chip (my chip runs, PR 48
# c1, c3, c4: eight seeds; r1, r2: after the review; PERF.md section 2):
# - logits, 24 positions a run (short, long, resumed): 0.028-0.044 over
#   eight seeds (192 positions), a run's median 0.033.  The wrong models
#   against the true reference on the same positions, as a run's median: the
#   state zeroed at the boundary 0.88-1.38, ``D`` = 0 1.14-1.73, the mixer
#   dropped and ``ssm_multipliers`` all 1 over 3.2.  LOGIT_ATOL 0.15 lies
#   3.4x above the largest served reading and 6x below the least of a
#   control's medians.  A bfloat16 STATE passes it (0.010-0.025: four
#   layers of bf16 activations hide it), which is why rule 3 exists.
# - whole pages (state / filter rows, a layer each): served 0.0038-0.0118 /
#   0.0024-0.0068, rising with depth (bf16 x, B and dt upstream of an fp32
#   state); the state zeroed at the boundary 0.22-0.54 forty tokens behind
#   it, ``D`` = 0 0.16-0.33 from layer 1 on.  PAGE_RTOL 0.05: 4.2x above the
#   one, 3-4x below the others.
# - the state's own arithmetic (rule 3; r1, r2: the readings after the
#   review).  ONE comparison against the reference's float32 steps cannot
#   hold it: the served page then reads 0.47-0.66% (r1: the steps' own bf16
#   roundings, at places the compiler chooses) where the reference's
#   bfloat16 state reads 0.42-0.86% of the true one: no limit lies between.
#   So two links.  The STEPS (the program's x, B, dt against the
#   reference's, float32 from the weights): 0.0028-0.0029 on every run of
#   tokens (24 over eight seeds); the reference's steps held in 8 bits
#   (e4m3) 0.0456-0.0470.  STEPS_RTOL 2^-6 = 0.0156: 5.4x above the one,
#   2.9x below the other.  The STATE on
#   those steps: (a) after 64 in-place decode updates 0.0, bit for bit, in
#   every run (the kernel and the reference add the same float32 terms in
#   the same order); carried in bfloat16 3.5e-3 to 8.3e-3.  STATE_RTOL 2^-12
#   = 2.4e-4: an order of magnitude under the control, far over float32's
#   6e-8 a step.  (b) after a chunk program of 512 / 376 rows 1.6e-5 to
#   1.1e-4 over eight seeds (three bf16 passes a product and another order
#   of summation); carried in bfloat16 3.8e-3 to 2.3e-2; THE PROGRAM with
#   the scan's products at default precision (one bf16 pass:
#   ``_scratch/p48_lowscan.py``, r2a) 1.42e-3 to 1.44e-3 where the
#   committed scan read 3.9e-5 to 4.3e-5 at the same seed.  All three move
#   together from seed to seed (the served reading is 0.4-0.7% of the
#   bfloat16 one, the single pass 17-19%).  CHUNK_RTOL 2^-11 = 4.9e-4: 4.4x
#   above the largest served reading, 2.9x below the single-pass scan, 7.8x
#   below the least bfloat16 state.
# - an emitted token lies within twice the logits' error of the reference's
#   largest logit: read 1.4e-6 to 0.018 over 16 requests of 67-98 tokens (it
#   IS the reference's argmax, or ties with it); a token from a wrong slot or
#   a stale page is a random one, ~4.7 below the largest of 261,120 (sd 1).
#   TOKEN_GAP = 2 x LOGIT_ATOL.
LOGIT_ATOL = 0.15
PAGE_RTOL = 0.05
STATE_RTOL = 2.0 ** -12
CHUNK_RTOL = 2.0 ** -11
STEPS_RTOL = 2.0 ** -6
TOKEN_GAP = 2 * LOGIT_ATOL
N_SHORT = 8
SHORT_LEN = 300
LONG_LEN = 1400
RESUMED_MESSAGE = 40
RESUMED_STEPS = 5
CARRIED_STEPS = 64
WIDTH = 2048              # one padded row for every reference call
SPANS = serve_runner.SPANS
FAULTS = ("no_ssm", "d_zero", "unit_ssm_multipliers", "bf16_state")
# What ``lib/ssm_costs.py`` and ``lib/afmoe_costs.py`` read, under their keys.
SSM_KEYS = ("mamba_n_heads", "mamba_d_state", "mamba_d_head",
            "mamba_n_groups", "mamba_chunk_size", "num_hidden_layers")
ATTEND_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "num_hidden_layers", "hidden_size")


def model_config(sizes: dict):
    """The program's FalconH1Config from the configuration file: the
    published keys as published."""
    return falcon_h1_model.FalconH1Config.from_hf(sizes)


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: falcon_h1_model.falcon_h1_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def system_prompts(items, tokens: int) -> list:
    """The traffic's shared system prompts, by index, from the requests
    that begin with them (the generator draws them inside)."""
    found = {}
    for r in items:
        if r["shared"] >= 0:
            found.setdefault(int(r["shared"]), r["prompt"][:tokens].copy())
    return [found[k] for k in sorted(found)]


def _reference(engine, sizes, width: int, n_out: int, fault=None):
    """One compiled reference for token rows padded to ``width`` (causal:
    padding after the real tokens changes nothing before it) and ``n_out``
    output positions: (logits, (states, filter rows) at ``state_at``);
    ``zero_state_at`` is traced (0: the true model)."""
    fn = jax.jit(lambda p, t, out, at, cut: reference.forward(
        p, t, sizes, out_positions=out, q_block=128, fault=fault,
        state_at=at, zero_state_at=cut))

    def run(tokens, out_positions, state_at=0, zero_state_at=0):
        row = np.zeros(width, np.int32)
        row[:len(tokens)] = tokens
        out = np.zeros(n_out, np.int32)
        out[:len(out_positions)] = out_positions
        lg, (ssm, conv) = fn(
            engine._params, jnp.asarray(row), jnp.asarray(out),
            jnp.int32(state_at), jnp.int32(zero_state_at))
        return (np.asarray(lg)[:len(out_positions)], np.asarray(ssm),
                np.asarray(conv))
    return run


def _token_gaps(engine, sizes, width: int, n_out: int):
    """One compiled teacher-forced reference for rows padded to ``width``:
    per emitted token, the reference's largest logit minus that token's."""
    def gaps(p, t, out, nxt):
        h, _ = reference.hidden(p, t, sizes, q_block=128)
        return reference.token_gaps(p, h, sizes, out, nxt)
    fn = jax.jit(gaps)

    def run(prompt, out_tokens):
        plen, n = len(prompt), len(out_tokens)
        row = np.zeros(width, np.int32)
        row[:plen] = prompt
        row[plen:plen + n] = out_tokens
        out = np.zeros(n_out, np.int32)
        out[:n] = np.arange(plen - 1, plen + n - 1)
        nxt = np.zeros(n_out, np.int32)
        nxt[:n] = out_tokens
        return np.asarray(fn(engine._params, jnp.asarray(row),
                             jnp.asarray(out), jnp.asarray(nxt)))[:n]
    return run


_take_page = jax.jit(lambda pool, g, page: pool[:, g, page].astype(
    jnp.float32))


def _page(engine, slot):
    """The stream's page as float32: (state ``[layers, nh, N, P]``, filter
    rows ``[layers, d_conv - 1, conv_dim]``)."""
    g, page = engine.group_of(slot), int(engine.block_tables[slot][-1])
    names = engine.cache_specs[-1].pool_names
    ssm = np.asarray(_take_page(engine.cache[names[0]], g, page))
    conv = np.asarray(_take_page(engine.cache[names[1]], g, page))
    cfg = engine.model_cfg
    return ssm, conv.reshape(conv.shape[0], cfg.mamba_d_conv - 1,
                             cfg.conv_dim)


def _through_the_cache(engine, prompt, steps: int, more: int = 0,
                       by_chunk=None):
    """(tokens emitted, logits of the prefill and of ``steps`` decode
    iterations, admission info, the page after prefill, after the last of
    those iterations and, with ``more``, after that many further ones) of
    ``prompt`` served alone through the engine's own admission, prefill and
    decode.  ``by_chunk``: a list that receives the stream's page after
    EVERY chunk program of the admission (the engine's compiled prefill
    step is watched for its duration: each call's pools are read before the
    next call donates them)."""
    slot = engine.select_slot(prompt, 1 + steps + more)
    step = engine._prefill_fn
    if by_chunk is not None:
        def watched(*args):
            out = step(*args)
            engine._store_pools(out[:len(engine._cache_sh)])
            by_chunk.append(_page(engine, slot))
            return out
        engine._prefill_fn = watched
    try:
        tok, pre = engine.prefill(prompt, slot, return_logits=True,
                                  max_new_tokens=1 + steps + more)
    finally:
        engine._prefill_fn = step
    info = dict(engine.last_admit_info(slot))
    page0 = _page(engine, slot)
    engine.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre, np.float32)]
    for _ in range(steps):
        sampled, dec = engine.decode_once(return_logits=True)
        toks.append(int(sampled[slot]))
        got.append(np.asarray(dec[slot], np.float32))
    page1 = _page(engine, slot)
    for _ in range(more):
        toks.append(int(engine.decode_once()[0][slot]))
    page2 = _page(engine, slot) if more else None
    engine.release_slot(slot)
    if more:
        return toks, np.stack(got), info, page0, page1, page2
    return toks, np.stack(got), info, page0, page1


def state_errors(engine, sizes, width: int):
    """-> ``errors(before, after, tokens, first, count)`` over the run
    ``tokens[first : first + count]`` of one stream, LAYER 0, as a dict:
    ``steps``: the x, B and dt the PROGRAM's embedding, norm, projection and
    filter give for the run (``before``'s filter rows ahead of it) against
    ``reference.first_layer_steps`` in float32, the largest of the three
    relative errors; ``steps_e4m3``: the same of the reference's own steps
    held in 8 bits (the control); ``state``: ``after``'s state against
    ``before``'s carried over the run by ``reference.carry_state`` in
    float32 on the program's steps; ``state_bf16``: the same of that state
    carried in bfloat16 (the control).  One compiled program for runs of up
    to ``width`` tokens: the rows past ``count`` count for nothing."""
    from deepspeed_tpu.models.blocks import rms_norm
    cfg = engine.model_cfg
    taps = int(sizes["mamba_d_conv"])

    def rel(a, b):
        return jnp.sqrt(jnp.square(a - b).sum() / jnp.square(b).sum())

    @jax.jit
    def errors(params, S0, rows0, S1, row, count):
        live = jnp.arange(width) < count
        p = params["layers"][0]
        h = (params["embed"][row[taps - 1:]].astype(jnp.float32)
             * cfg.embedding_multiplier).astype(cfg.dtype)
        _, xbc, dt_raw = falcon_h1_model.ssm_in(
            p, rms_norm(h, p["input_norm"], cfg.rms_norm_eps), cfg)
        rows = jnp.concatenate([rows0.astype(xbc.dtype), xbc])
        x, B, _ = falcon_h1_model.ssm_split(
            falcon_h1_model.ssm_conv(p, rows, cfg), cfg)
        dt, a = falcon_h1_model.ssm_steps(p, dt_raw)
        dt = jnp.where(live[:, None], dt, 0.0)
        decay = jnp.where(live[:, None], jnp.exp(a), 1.0)
        want = reference.carry_state(S0, x, B, dt, decay)
        low = reference.carry_state(S0, x, B, dt, decay, cast=jnp.bfloat16)
        # ... and those steps against the reference's own, row by live row.
        true = reference.first_layer_steps(params, row, sizes,
                                           skip=taps - 1)
        rough = reference.first_layer_steps(params, row, sizes,
                                            skip=taps - 1,
                                            act=jnp.float8_e4m3fn)

        def worst(got):
            def of_live(v):
                return jnp.where(live.reshape((-1,) + (1,) * (v.ndim - 1)),
                                 v.astype(jnp.float32), 0.0)
            return jnp.stack([rel(of_live(g), of_live(t))
                              for g, t in zip(got, true)]).max()
        return (rel(S1, want), rel(low, want), worst((x, B, dt)),
                worst(rough[:3]))

    def run(before, after, tokens, first: int, count: int):
        assert first >= taps - 1 and count <= width, (first, count)
        row = np.zeros(taps - 1 + width, np.int32)
        row[:taps - 1 + count] = tokens[first - (taps - 1):first + count]
        out = errors(engine._params, before[0][0], before[1][0],
                     after[0][0], jnp.asarray(row), jnp.int32(count))
        return dict(zip(("state", "state_bf16", "steps", "steps_e4m3"),
                        (float(v) for v in out)))
    return run


def _rows(name, got, want, vocab):
    return [(f"{name}.{j}", float(np.abs(got[j, :vocab]
                                         - want[j, :vocab]).max()))
            for j in range(len(got))]


def _page_rows(name, page, want):
    """[(what.layer, state's relative error, filter rows')] of a page
    against the reference's, a layer each."""
    def rel(a, b):
        axes = tuple(range(1, a.ndim))
        return np.sqrt(np.square(a - b).sum(axes)
                       / np.maximum(np.square(b).sum(axes), 1e-30))
    return [(f"{name}.{layer}", float(s), float(c)) for layer, (s, c)
            in enumerate(zip(rel(page[0], want[0]), rel(page[1], want[1])))]


def check_against_reference(engine, sizes, system, vocab: int, seed: int):
    """(logit rows [(group.what, |logit error| max)], page rows
    [(group.what.layer, state error, filter rows' error)], {control: (logit
    rows, page rows)}, facts about the resumed prompt)."""
    rng = np.random.default_rng([seed, 3])
    short = min(SHORT_LEN, engine.max_len // 4)
    long_ = min(LONG_LEN, engine.max_len // 2)
    width = min(WIDTH, engine.max_len)
    ref = _reference(engine, sizes, width, 1 + RESUMED_STEPS)
    rows, pages = [], []
    controls = {name: ([], []) for name in FAULTS + ("state_zeroed",)}
    wrong = {name: _reference(engine, sizes, width, 1 + RESUMED_STEPS,
                              fault=name) for name in FAULTS}
    carried_by = state_errors(engine, sizes, engine.prefill_chunk)
    chunks = []
    for i in range(N_SHORT + 1):                      # short, then long
        n, name = (short, f"short{i}") if i < N_SHORT else (long_, "long")
        prompt = rng.integers(0, vocab, size=n, dtype=np.int32)
        by_chunk = [] if name == "long" else None
        toks, got, _, page0, _ = _through_the_cache(engine, prompt, 1,
                                                    by_chunk=by_chunk)
        if by_chunk:
            # every chunk program but the first, from the page before it
            chunk = engine.prefill_chunk
            chunks = [carried_by(by_chunk[k - 1], by_chunk[k], prompt,
                                 k * chunk, min(chunk, n - k * chunk))
                      for k in range(1, len(by_chunk))]
        seq, at = np.concatenate([prompt, toks[:1]]), [n - 1, n]
        want, ssm, conv = ref(seq, at, state_at=n - 1)
        rows += _rows(name, got, want, vocab)
        pages += _page_rows(name, page0, (ssm, conv))
        if i < 2:
            # The controls: what a wrong model reads against the TRUE
            # reference (a bfloat16 state too: what of it logits and whole
            # pages see is printed beside the rest).
            for fault, run in wrong.items():
                low, low_ssm, low_conv = run(seq, at, state_at=n - 1)
                controls[fault][0].extend(_rows(name, low, want, vocab))
                controls[fault][1].extend(_page_rows(
                    name, (low_ssm, low_conv), (ssm, conv)))
    # resumed: a message behind a cached system prompt.
    boundary = len(system[0])
    prompt = np.concatenate([system[0], rng.integers(
        0, vocab, size=min(RESUMED_MESSAGE, engine.max_len // 16),
        dtype=np.int32)])
    more = min(CARRIED_STEPS, engine.max_len // 8)
    toks, got, info, page0, page1, page2 = _through_the_cache(
        engine, prompt, RESUMED_STEPS, more)
    # (the token an iteration consumes is the one the iteration before
    # emitted: ``more`` updates from page1 to page2)
    carried = carried_by(page1, page2, np.concatenate([prompt, toks]),
                         len(prompt) + RESUMED_STEPS, more)
    seq = np.concatenate([prompt, toks[:RESUMED_STEPS]])
    at = [len(prompt) - 1 + i for i in range(1 + RESUMED_STEPS)]
    want, ssm0, conv0 = ref(seq, at, state_at=at[0])
    _, ssm1, conv1 = ref(seq, at, state_at=at[-1])
    rows += _rows("resumed", got, want, vocab)
    pages += _page_rows("resumed_prefill", page0, (ssm0, conv0))
    pages += _page_rows("resumed_last", page1, (ssm1, conv1))
    # What a stream that resumed WITHOUT its snapshot would have computed.
    low, low_ssm, low_conv = ref(seq, at, state_at=at[0],
                                 zero_state_at=boundary)
    controls["state_zeroed"][0].extend(_rows("resumed", low, want, vocab))
    controls["state_zeroed"][1].extend(_page_rows(
        "resumed_prefill", (low_ssm, low_conv), (ssm0, conv0)))
    facts = {"boundary": boundary, "carried_steps": more,
             "state_carried": carried["state"],
             "state_carried_bf16": carried["state_bf16"],
             "chunks_carried": [c["state"] for c in chunks],
             "chunks_carried_bf16": [c["state_bf16"] for c in chunks],
             "steps": [c["steps"] for c in [carried] + chunks],
             "steps_e4m3": [c["steps_e4m3"] for c in [carried] + chunks],
             "resumed_at": info.get("cached_tokens", 0),
             "cached_by_class": info.get("cached_by_class"),
             "lost_to_kind_tokens": info.get("lost_to_kind_tokens"),
             "chunks": info.get("chunks")}
    return rows, pages, controls, facts


def logits_agree(rows) -> bool:
    return bool(rows) and max(err for _, err in rows) <= LOGIT_ATOL


def pages_agree(pages) -> bool:
    return bool(pages) and max(max(s, c) for _, s, c in pages) <= PAGE_RTOL


def summary(rows, pages) -> dict:
    """What the two rules count, for the ``phase: serve`` line."""
    groups = {}
    for name, err in rows:
        groups.setdefault(name.rstrip("0123456789.").split(".")[0],
                          []).append(err)
    layers = {}
    for name, s, c in pages:
        layers.setdefault(int(name.rsplit(".", 1)[1]), []).append((s, c))
    return {"logit_max_by_group": {g: max(v) for g, v in groups.items()},
            "logit_median": float(np.median([e for _, e in rows]))
            if rows else None,
            "state_max_by_layer": [max(s for s, _ in layers[l])
                                   for l in sorted(layers)],
            "filter_rows_max_by_layer": [max(c for _, c in layers[l])
                                         for l in sorted(layers)]}


def pick_served(reqs, shared_of, width: int):
    """The two requests whose every emitted token is checked: the
    latest-started FINISHED one behind a system prompt and the
    latest-started finished one that shares nothing, of those that fit the
    reference's row."""
    done = sorted((r for r in reqs if r.t_first is not None
                   and len(r.out_tokens) >= r.max_new_tokens
                   and len(r.prompt) + len(r.out_tokens) <= width),
                  key=lambda r: r.t_first)
    latest = {}
    for r in done:
        latest[shared_of[r.rid] >= 0] = r
    return list(latest.values())


def run(ctx):
    tr = ctx.traffic
    sizes = dict(ctx.config)
    vocab = int(ctx.config["vocab_size"])
    cfg, engine = build_engine(ctx)
    bs = engine.block_size
    ctx.mark("weights_and_engine")
    serve_runner.warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)

    items = reason_traffic.requests(tr, ctx.seed, ctx.seconds, vocab)
    system = system_prompts(items, int(tr["shared_prefix"]["tokens"]))
    engine.serve(serve_runner._requests([
        {"rid": -100 - i, "prompt": p, "max_new_tokens": 1, "arrival_s": 0.0}
        for i, p in enumerate(system)]))
    # (a message's first token stands in for the message: the boundary a
    # request behind the system prompt would resume at, in both kinds)
    system_cached = [
        engine.prefix_match_tokens(np.concatenate([p, [0]]))
        == len(p) // bs * bs for p in system]
    ctx.mark("system_prompts")

    rows, pages, controls, facts = check_against_reference(
        engine, sizes, system, vocab, ctx.seed)
    ctx.mark("reference")
    engine.reset_serving_stats()
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], backlog=tr["backlog"],
            system_prompts=len(system),
            state_page_bytes=engine.cache_specs[-1].block_nbytes(),
            state_page_tokens=engine.cache_specs[-1].page_tokens)

    tracer = None
    if ctx.trace:
        engine.prefill_many = serve_runner._annotated(
            "prefill_many", engine.prefill_many)
        engine.decode_once = serve_runner._annotated(
            "decode_once", engine.decode_once)

        def traced_window():
            time.sleep(ctx.seconds * float(tr["trace_at_fraction"]))
            _common.start_trace(ctx.trace_dir)
            time.sleep(float(tr["trace_seconds"]))
            jax.profiler.stop_trace()
        tracer = threading.Thread(target=traced_window, daemon=True)

    compiles_setup = dict(ctx.compile_events)
    ctx.compile_events.clear()
    classes0 = _class_state(engine)
    totals0 = engine.allocator.snapshot_totals()
    setup_s = time.perf_counter() - ctx.t0
    if tracer:
        tracer.start()
    reqs, report, wall, live, live_by_class = measure(engine, items,
                                                      ctx.seconds)
    if tracer:
        tracer.join()
    compiles_window = int(ctx.compile_events.get("n", 0))

    s = serve_runner.summarize(reqs, wall)
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "cache_classes", "state")}
    classes1 = _class_state(engine)
    totals1 = engine.allocator.snapshot_totals()
    by_class = {}
    for name, st in classes1.items():
        seen = [row[name]["live"] for row in live_by_class if name in row]
        later = seen[len(seen) // 2:]
        by_class[name] = {
            "num_blocks": st["blocks"],
            "live_blocks_mean": float(np.mean(later)) if later else None,
            "live_blocks_max": max(seen, default=None),
            "reclaimed_in_window":
                st["reclaimed"] - classes0[name]["reclaimed"]}
    half = live[len(live) // 2:]
    kv = {"num_blocks": int(sum(st["blocks"] for st in classes1.values())),
          "block_bytes": {sp.name: sp.block_nbytes()
                          for sp in engine.cache_specs},
          "live_blocks_mean": float(np.mean(half)) if half else None,
          "live_blocks_max": max(live, default=None),
          "live_blocks_by_second": live, "classes": by_class,
          "system_prompts_cached": int(sum(system_cached))}
    # The window's admissions across kinds, from the program's counters.
    state = report.get("state") or {}
    prefix = report.get("prefix") or {}
    window = {
        "admissions": sum(r.t_first is not None for r in reqs),
        "snapshots_taken": totals1.get("snapshots_taken", 0)
        - totals0.get("snapshots_taken", 0),
        "snapshot_hits": totals1.get("snapshot_hits", 0)
        - totals0.get("snapshot_hits", 0),
        "snapshots_evicted": totals1.get("snapshots_evicted", 0)
        - totals0.get("snapshots_evicted", 0),
        "resumed_tokens": state.get("resumed_tokens"),
        "prefix_lost_to_kind_tokens":
            state.get("prefix_lost_to_kind_tokens"),
        "cached_tokens": prefix.get("cached_tokens")}
    peak_window = _common.memory_peak_bytes(ctx.devices)

    # The teacher-forced rows are max_total wide and float32: the pools
    # have done their work and make room for them.
    engine.cache.clear()
    width = -(-int(tr["max_total"]) // 128) * 128
    gaps_of = _token_gaps(engine, sizes, width, int(tr["output_len"]["max"]))
    served, wrong = [], 0
    for r in pick_served(reqs, {r["rid"]: r["shared"] for r in items},
                         width):
        gap = gaps_of(r.prompt, r.out_tokens)
        wrong += float(gap.max()) > TOKEN_GAP
        served.append((r.rid, len(r.prompt), len(r.out_tokens),
                       float(gap.max()), float(np.percentile(gap, 99))))
    agree, pages_ok = logits_agree(rows), pages_agree(pages)
    # The controls have to fail the comparisons the system has to pass, on
    # the same positions.
    state_ok = facts["state_carried"] <= STATE_RTOL \
        and len(facts["chunks_carried"]) > 0 \
        and max(facts["chunks_carried"]) <= CHUNK_RTOL \
        and max(facts["steps"]) <= STEPS_RTOL
    controls_fail = {
        "bf16_state.carried": facts["state_carried_bf16"] > STATE_RTOL,
        "bf16_state.chunks": min(facts["chunks_carried_bf16"],
                                 default=0.0) > CHUNK_RTOL,
        "e4m3_steps": min(facts["steps_e4m3"]) > STEPS_RTOL,
        "state_zeroed.logits": not logits_agree(controls["state_zeroed"][0]),
        "state_zeroed.pages": not pages_agree(controls["state_zeroed"][1]),
        **{f"{name}.logits": not logits_agree(controls[name][0])
           for name in FAULTS if name != "bf16_state"}}
    b = facts["boundary"]
    resumed = facts["resumed_at"] == b \
        and set((facts["cached_by_class"] or {}).values()) == {b}
    correct = s["failed"] == 0 and wrong == 0 and len(served) == 2 \
        and agree and pages_ok and state_ok \
        and all(controls_fail.values()) and resumed \
        and all(system_cached) and compiles_window == 0 \
        and s["output_tokens"] > 0
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, logits_agree=agree, page_checks=pages,
            pages_agree=pages_ok, state_agrees=state_ok,
            summary=summary(rows, pages),
            controls={name: summary(lg, pg)
                      for name, (lg, pg) in controls.items()},
            controls_fail=controls_fail, facts=facts, resumed=resumed,
            window=window,
            limits={"logit": LOGIT_ATOL, "page_rtol": PAGE_RTOL,
                    "state_rtol": STATE_RTOL, "chunk_rtol": CHUNK_RTOL,
                    "steps_rtol": STEPS_RTOL,
                    "token_gap": TOKEN_GAP},
            served_tokens_checked=served, paged_kernel=engine.paged_kernel,
            max_slots=engine.max_slots, prefill_chunk=engine.prefill_chunk,
            kv=kv, memory_peak_bytes_at_window_end=peak_window,
            offered_tokens_per_s=sum(r.max_new_tokens for r in reqs)
            / ctx.seconds, snapshot=snapshot, **s)

    record = {
        "kind": "serve", "correct": correct, "attempted": s["attempted"],
        "failed": s["failed"] + wrong,
        "end_to_end": {"serve_tokens_per_s": s["tokens_per_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "summary": s, "snapshot": snapshot, "kv": kv, "sessions": window,
        "ssm": {k: ctx.config[k] for k in SSM_KEYS},
        "afmoe": {k: ctx.config[k] for k in ATTEND_KEYS},
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

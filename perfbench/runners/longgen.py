"""kind: longgen -- tasks of about a thousand tokens answered with thousands,
a few of them questions over a very long cached document, from a standing
backlog, through ``InferenceEngine.serve``, for a configuration of the
``kimi_linear`` family: three Kimi-Delta-Attention layers (a gated DELTA
rule over a float32 state a stream, a decay a channel) to one NoPE
latent-attention layer (a ``[c | k_pe]`` row a token), over expert layers
that hold a share of their experts — two KINDS of cache in different layers,
one manager, one prefix rule.

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile the prefill chunk and the decode
step; EVERY SYSTEM PROMPT AND EVERY DOCUMENT SERVED ONCE (1 new token)
through ``engine.serve`` so that its latent blocks and its state snapshot at
its last block boundary sit in the prefix cache (a hit needs both kinds);
the float32 reference comparison and its controls (its document question
builds the page copy); ``reset_serving_stats()``.  Window: ``backlog``
requests due at 0 and an open loop over ``[0, --seconds)`` at the traffic
file's fixed rate, above what the system sustains, cut by the scheduler at
the window's end (``lib/longgen_traffic.py``): decode of ``max_slots``
streams whose state traffic is constant while a few of them read 16k-49k
latent rows.  After the window: every emitted token of two requests served
inside the full batch against the reference.

``correct`` (decided on the chip at the published widths, from what the
timed path produced; logits and pages, not tokens), every part of it:
1. logits through BOTH kinds of cache against the reference's full forward
   (``lib/kimi_linear_reference.py``: the delta rule token by token) in
   three groups: ``short``: the prefill and first decode of ``N_SHORT``
   unshared prompts of ``SHORT_LEN`` tokens (two chunk programs); ``long``:
   one unshared prompt of ``LONG_LEN`` tokens (four chunk programs, the
   state carried from one to the next); ``doc``: a question over the
   SHORTEST document through the hit path (it must have resumed at the
   document's last block boundary in BOTH classes: latent blocks by
   reference + the snapshot copied), its prefill, its first ``DOC_STEPS``
   decode iterations and, ``CARRIED_STEPS`` iterations on, ``TAIL_STEPS``
   more, against the reference over the whole sequence from position 0.
   Seven expert layers of top-8-of-256 flip near-ties, and a flip travels on
   through the state, so (``logits_agree``): the MEDIAN error within
   ``MEDIAN_ATOL``; every position whose routing the reference finds DECIDED
   (margin >= ``MARGIN_EPS``) within ``LOGIT_ATOL``; at least ``CLEAN_MIN``
   of all positions within ``LOGIT_ATOL``; none over ``FLIP_ATOL``;
2. the state PAGES of those streams (after prefill; the doc stream after its
   last checked iteration too) against the reference's ``S_t`` and filter
   rows, a KDA layer each, by relative error (Frobenius): their MEDIAN over
   streams within ``PAGE_RTOL`` a layer and none over ``PAGE_FLIP_RTOL`` (a
   flip upstream moves a page as it moves a logit) (``pages_agree``);
3. the state's OWN arithmetic, apart from everything upstream of it, in two
   links over LAYER 1 (a KDA layer with no expert layer ahead of it).  THE
   STEPS: the q, k, v, g and beta that the program's embedding, norm,
   projection, filters and gates give for a run of tokens against
   ``reference.first_layer_steps`` (float32, from the weights alone), a
   relative error each, within ``STEPS_RTOL`` — computed by the program's
   OWN FUNCTIONS (``models.kimi_linear.kda_in`` / ``kda_conv`` / ``kda_qkv``
   / ``kda_gates``) inside a jit of this check, NOT by the timed prefill and
   decode programs: what ties the steps to the served pages is the second
   link, which carries the timed programs' page over them.  THE STATE: the
   page the program holds after the run against the page it held before
   it, carried over the run by ``reference.carry_state`` (float32, token by
   token) on those steps: (a) DECODE, the doc stream's page after
   ``CARRIED_STEPS`` in-place updates of the kernel, within ``STATE_RTOL``; (b) PREFILL, the
   ``long`` stream's page after EVERY chunk program but the first against
   its page after the program before (the chunked delta rule over up to
   ``prefill_chunk`` rows from a carried state), within ``CHUNK_RTOL``;
4. the comparison can fail, shown every run on the same positions, the
   reference's wrong models read against the true reference under the same
   rules: the state carried in BFLOAT16 must fail 3 (a) and 3 (b); the
   reference's steps held in 8 bits (e4m3) must fail 3's first link; the
   delta term dropped, one decay a head, ``alpha`` = 1 and rotary ON in the
   latent layers must each fail 1 on the ``short`` prompts; the state ZEROED
   at the snapshot's boundary must fail 1 and 2 on the ``doc`` stream;
5. every emitted token of two FINISHED requests served inside the full batch
   (the latest-started one behind a document and the latest-started one
   that shares nothing, of those that fit ``SERVED_WIDTH``) against the
   reference's teacher-forced forward: none further below its largest logit
   than ``TOKEN_GAP_MAX`` and at most ``TOKEN_SHARE`` of a request's tokens
   further than ``TOKEN_GAP`` (``tokens_agree``);
6. every system prompt and document resumable at its boundary in both
   classes when the window opens AND when it has closed, no request over its
   length, zero compiles in the window, some output.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import kimi_linear as kimi_model  # fails at once on
#                a program that has no such family: nothing has run yet
from perfbench.lib import kimi_linear_reference as reference
from perfbench.lib import longgen_traffic, traffic as traffic_lib, xplane
from perfbench.runners import _common, serve as serve_runner
from perfbench.runners.chat_state import _page_rows, _take_page
from perfbench.runners.mixed_docqa import _class_state, measure

# Served logits (bf16 weights, activations, latent and filter rows; fp32
# state, gates, norms, softmax, the triangular solve and accumulation) against
# the float32 reference on the same bf16 weights upcast.  Logits of the seeded
# model have a standard deviation of about 1 (``kimi_linear_init``).  Read on
# the chip (PERF.md section 2 has every reading with its run):
# - logits, 20 positions a run (short, long, doc), twenty-two runs (c2-c7,
#   r1, r2): a run's median 0.059-0.107; positions that read over 0.15 (a
#   routing decision flipped there or, for a long prompt and the document,
#   anywhere upstream: 16 of 256 experts are held, and one of eight weights
#   swapped moves less than in cells 4 and 7): 25 of 440, 0.152-0.624, all
#   but one at margins under 0.004 (the one, 0.171 at 0.0113, is the decode
#   step behind a flipped prefill).  The wrong models against the true
#   reference on the same positions, as (least position, a run's median,
#   largest position) over the ten runs of r1 and r2 (the medians and the
#   largest over all twenty-two): the state zeroed at the boundary
#   0.565-0.831 / 1.09-1.37 / 1.25-1.73; rotary on 0.94-1.09 / 1.05-1.25 /
#   1.13-1.50; the delta term dropped 1.83-2.21 / 1.96-2.39 / 2.01-2.68; one
#   decay a head 2.13-2.50 / 2.36-2.75 / 2.47-3.06; alpha = 1 3.15-3.61 /
#   3.43-3.93 / 3.62-4.16.  Every limit lies between what the served path reads and
#   what the mildest wrong model reads:
#   MEDIAN_ATOL 0.2: 1.9x above 0.107, 5x below 1.05.
#   LOGIT_ATOL 0.4, on every position whose routing is decided and on at
#   least CLEAN_MIN of all (read 0.90-1.0; every control 0.0): 3x above the
#   largest decided position (0.135), 1.4x below the least position of any
#   control (0.565).
#   MARGIN_EPS 0.012 (what "decided" means): every position that read over
#   0.4 had a margin of 0.0032 or less (3.7x below); 1-9 of a run's 20
#   positions are decided at 0.012, 0-4 at 0.03, where the clause would run
#   empty.
#   FLIP_ATOL 1.0, on every position: 1.6x above the largest position read
#   (0.624), under the LARGEST position of every control in every run (the
#   least of them 1.13, rotary on): a wrong model fails this clause too, on
#   its own.
# - whole pages (state / filter rows, a KDA layer each, ten streams a run):
#   the median over streams 0.0035-0.041 rising with depth (layers 5-7 sit
#   behind a latent layer and expert layers), the largest 0.084; the state
#   zeroed at the boundary 0.30-0.59, rotary on 0.26-0.30 in the three layers
#   behind the first latent layer, the other three over 0.74.  PAGE_RTOL 0.1
#   on a layer's median: 3x above the one, 2.6x below the least other;
#   PAGE_FLIP_RTOL 0.5 on any one page (a flip upstream).
# - the state's own arithmetic (rule 3).  The STEPS (the program's q, k, v,
#   g, beta against the reference's float32): 0.00250-0.00261 on every run of
#   tokens; the reference's steps held in 8 bits (e4m3) 0.0500-0.0517.
#   STEPS_RTOL 2^-6 = 0.0156: 6x above, 3.3x below.  The STATE on those
#   steps: (a) after 64 in-place decode updates 1.2e-7 (float32, another
#   order of summation); carried in bfloat16 7.0e-3 to 7.9e-3.  STATE_RTOL
#   2^-12 = 2.4e-4.  (b) after a chunk program of 512 / 264 rows 4.2e-5 to
#   6.0e-5 (r1, r2, ten runs: three bf16 passes a product against the state,
#   six for the pairs inside a sub-block and the triangular inverse);
#   carried in bfloat16 7.6e-3 to 9.1e-3; THE PROGRAM with the pairs inside
#   a sub-block at ONE bf16 pass (their einsum without a ``precision``)
#   2.5e-4 to 3.2e-4 (c2-c7, thirteen seeds; 2.77e-4 at the seed that reads
#   4.3e-5 at six passes); with
#   the products against the state at one pass too
#   (``_scratch/p52_lowchunk.py``, r1) 2.85e-3 to 2.92e-3.  CHUNK_RTOL 2^-13
#   = 1.22e-4: 2.0x above the largest served reading, 2.0x below the pairs
#   at one pass, 23x below everything at one pass, 62x below the bfloat16
#   state.
# - an emitted token: where the served logits are within e of the
#   reference's, the token lies within 2e of the reference's largest logit:
#   read 0.13-0.41 as the LARGEST gap of twenty requests of 688-1,410 tokens,
#   none over 1.0.  A token from a wrong slot or a stale page is a random one: ~3.9
#   below the largest of 20,480 (sd 1), over TOKEN_GAP 1.0 nearly every time:
#   at most TOKEN_SHARE of a request's tokens may be (what a flip's aftermath
#   could leave); TOKEN_GAP_MAX stops what is no number at all.
MEDIAN_ATOL = 0.2
LOGIT_ATOL = 0.4
FLIP_ATOL = 1.0
MARGIN_EPS = 0.012
CLEAN_MIN = 0.75
PAGE_RTOL = 0.1
PAGE_FLIP_RTOL = 0.5
STATE_RTOL = 2.0 ** -12
CHUNK_RTOL = 2.0 ** -13
STEPS_RTOL = 2.0 ** -6
TOKEN_GAP = 1.0
TOKEN_SHARE = 0.10
TOKEN_GAP_MAX = 8.0
N_SHORT = 6
SHORT_LEN = 600
LONG_LEN = 1800
DOC_QUESTION = 40
DOC_STEPS = 3
CARRIED_STEPS = 64
TAIL_STEPS = 2
WIDTH = 2048              # one padded row for the short and long prompts
SERVED_WIDTH = 20480      # ... and for the teacher-forced rows
SPANS = serve_runner.SPANS
FAULTS = ("no_delta", "head_decay", "unit_alpha", "rotary_on")
# What ``lib/latent_costs.py`` reads, under its keys: ``num_hidden_layers``
# there multiplies a position's row bytes, so it is the count of LATENT
# layers (the layers that keep a row a token), not the model's depth.
LATENT_KEYS = ("kv_lora_rank", "qk_rope_head_dim", "num_attention_heads",
               "hidden_size", "moe_intermediate_size")


def model_config(sizes: dict):
    """The program's KimiLinearConfig from the configuration file: the
    published keys as published; the router's width is the PUBLISHED expert
    count, ``held`` the file's ``num_experts``."""
    return kimi_model.KimiLinearConfig.from_hf(
        sizes, num_experts=int(sizes["num_experts_published"]),
        held=(0, int(sizes["num_experts"])))


def reference_sizes(sizes: dict) -> dict:
    """The configuration file's dict as the reference reads it."""
    return dict(sizes, held=(0, int(sizes["num_experts"])))


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: kimi_model.kimi_linear_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def _reference(engine, sizes, width: int, n_out: int):
    """One compiled reference for token rows padded to ``width`` (causal:
    padding after the real tokens changes nothing before it) and ``n_out``
    output positions: (logits, routing margins, states, filter rows at
    ``state_at``); ``zero_state_at`` and ``fault`` are traced (0 / None: the
    true model), so the wrong models cost no program of their own."""
    fn = jax.jit(lambda p, t, out, at, cut, fault: reference.forward(
        p, t, sizes, out_positions=out, q_block=128, fault=fault,
        state_at=at, zero_state_at=cut))

    def run(tokens, out_positions, state_at=0, zero_state_at=0, fault=None):
        row = np.zeros(width, np.int32)
        row[:len(tokens)] = tokens
        out = np.zeros(n_out, np.int32)
        out[:len(out_positions)] = out_positions
        lg, margin, (S, conv) = fn(
            engine._params, jnp.asarray(row), jnp.asarray(out),
            jnp.int32(state_at), jnp.int32(zero_state_at),
            jnp.int32(reference.fault_code(fault)))
        n = len(out_positions)
        return (np.asarray(lg)[:n], np.asarray(margin)[:n], np.asarray(S),
                np.asarray(conv))
    return run


def _token_gaps(engine, sizes, width: int, n_out: int):
    """One compiled teacher-forced reference for rows padded to ``width``:
    per emitted token, the reference's largest logit minus that token's."""
    def gaps(p, t, out, nxt):
        h, _, _ = reference.hidden(p, t, sizes, q_block=128)
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


def _page(engine, slot):
    """The stream's page as float32: (state ``[kda layers, nh, dk, dv]``,
    filter rows ``[kda layers, taps - 1, conv_dim]``)."""
    g, page = engine.group_of(slot), int(engine.block_tables[slot][-1])
    names = engine.cache_specs[-1].pool_names
    state = np.asarray(_take_page(engine.cache[names[0]], g, page))
    conv = np.asarray(_take_page(engine.cache[names[1]], g, page))
    cfg = engine.model_cfg
    return state, conv.reshape(conv.shape[0], cfg.short_conv_kernel_size - 1,
                               cfg.conv_dim)


def _through_the_cache(engine, prompt, steps: int, more: int = 0,
                       tail: int = 0, by_chunk=None):
    """``prompt`` served alone through the engine's own admission, prefill
    and decode: ``steps`` iterations with logits, ``more`` without, ``tail``
    with.  Returns a dict: ``tokens`` emitted, ``logits`` of the prefill and
    of the iterations that kept theirs, admission ``info``, ``pages`` after
    the prefill, after ``steps``, after ``more`` and after ``tail``.
    ``by_chunk``: a list that receives the stream's page after EVERY chunk
    program of the admission (the engine's compiled prefill step is watched
    for its duration: each call's pools are read before the next call
    donates them)."""
    total = steps + more + tail
    slot = engine.select_slot(prompt, 1 + total)
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
                                  max_new_tokens=1 + total)
    finally:
        engine._prefill_fn = step
    info = dict(engine.last_admit_info(slot))
    pages = [_page(engine, slot)]
    engine.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre, np.float32)]
    for count, keep in ((steps, True), (more, False), (tail, True)):
        for _ in range(count):
            if keep:
                sampled, dec = engine.decode_once(return_logits=True)
                got.append(np.asarray(dec[slot], np.float32))
            else:
                sampled = engine.decode_once()[0]
            toks.append(int(sampled[slot]))
        pages.append(_page(engine, slot) if count else pages[-1])
    engine.release_slot(slot)
    return {"tokens": toks, "logits": np.stack(got), "info": info,
            "pages": pages}


def state_errors(engine, sizes, width: int):
    """-> ``errors(before, after, tokens, first, count)`` over the run
    ``tokens[first : first + count]`` of one stream, LAYER 1, as a dict:
    ``steps``: the q, k, v, g and beta the PROGRAM's embedding, norm,
    projection, filters and gates give for the run (``before``'s filter rows
    ahead of it) against ``reference.first_layer_steps`` in float32, the
    largest of the five relative errors; ``steps_e4m3``: the same of the
    reference's own steps held in 8 bits (the control); ``state``:
    ``after``'s state against ``before``'s carried over the run by
    ``reference.carry_state`` in float32 on the program's steps;
    ``state_bf16``: the same of that state carried in bfloat16 (the
    control).  One compiled program for runs of up to ``width`` tokens: the
    rows past ``count`` count for nothing."""
    from deepspeed_tpu.models.blocks import rms_norm
    cfg = engine.model_cfg
    taps = cfg.short_conv_kernel_size

    def rel(a, b):
        return jnp.sqrt(jnp.square(a - b).sum() / jnp.square(b).sum())

    @jax.jit
    def errors(params, S0, rows0, S1, row, count):
        live = jnp.arange(width) < count
        p = params["layers"][0]
        h = params["embed"].astype(cfg.dtype)[row[taps - 1:]]
        u = rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
        rows = jnp.concatenate([rows0.astype(cfg.dtype),
                                kimi_model.kda_in(p, u, cfg)])
        q, k, v = kimi_model.kda_qkv(kimi_model.kda_conv(p, rows, cfg), cfg)
        g, beta = kimi_model.kda_gates(p, u, cfg)
        g_live = jnp.where(live[:, None, None], g, 0.0)
        b_live = jnp.where(live[:, None], beta, 0.0)
        want = reference.carry_state(S0, q, k, v, g_live, b_live)
        low = reference.carry_state(S0, q, k, v, g_live, b_live,
                                    cast=jnp.bfloat16)
        # ... and those steps against the reference's own, row by live row.
        true = reference.first_layer_steps(params, row, sizes,
                                           skip=taps - 1)
        rough = reference.first_layer_steps(params, row, sizes,
                                            skip=taps - 1,
                                            act=jnp.float8_e4m3fn)

        def worst(got):
            def of_live(a):
                return jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)),
                                 a.astype(jnp.float32), 0.0)
            return jnp.stack([rel(of_live(a), of_live(t))
                              for a, t in zip(got, true)]).max()
        return (rel(S1, want), rel(low, want), worst((q, k, v, g, beta)),
                worst(rough))

    def run(before, after, tokens, first: int, count: int):
        assert first >= taps - 1 and count <= width, (first, count)
        row = np.zeros(taps - 1 + width, np.int32)
        row[:taps - 1 + count] = tokens[first - (taps - 1):first + count]
        out = errors(engine._params, before[0][0], before[1][0],
                     after[0][0], jnp.asarray(row), jnp.int32(count))
        return dict(zip(("state", "state_bf16", "steps", "steps_e4m3"),
                        (float(v) for v in out)))
    return run


def _rows(name, got, want, margin, vocab):
    return [(f"{name}.{j}", float(np.abs(got[j, :vocab]
                                         - want[j, :vocab]).max()),
             float(margin[j])) for j in range(len(got))]


def check_against_reference(engine, sizes, doc, vocab: int, seed: int):
    """(logit rows [(group.what, |logit error| max, routing margin)], page
    rows [(group.what.layer, state error, filter rows' error)], {control:
    (logit rows, page rows)}, facts about the doc stream and the links)."""
    rng = np.random.default_rng([seed, 5])
    short = min(SHORT_LEN, engine.max_len // 4)
    long_ = min(LONG_LEN, engine.max_len // 2)
    width = min(WIDTH, engine.max_len)
    n_out = 1 + DOC_STEPS + TAIL_STEPS
    ref = _reference(engine, sizes, width, n_out)
    rows, pages = [], []
    controls = {name: ([], []) for name in FAULTS + ("state_zeroed",)}
    more = min(CARRIED_STEPS, engine.max_len // 8)
    carried_by = state_errors(engine, sizes,
                              max(engine.prefill_chunk, more))
    chunks = []
    for i in range(N_SHORT + 1):                      # short, then long
        n, name = (short, f"short{i}") if i < N_SHORT else (long_, "long")
        prompt = rng.integers(0, vocab, size=n, dtype=np.int32)
        by_chunk = [] if name == "long" else None
        got = _through_the_cache(engine, prompt, 1, by_chunk=by_chunk)
        if by_chunk:
            # every chunk program but the first, from the page before it
            chunk = engine.prefill_chunk
            chunks = [carried_by(by_chunk[k - 1], by_chunk[k], prompt,
                                 k * chunk, min(chunk, n - k * chunk))
                      for k in range(1, len(by_chunk))]
        seq, at = np.concatenate([prompt, got["tokens"][:1]]), [n - 1, n]
        want, margin, S, conv = ref(seq, at, state_at=n - 1)
        rows += _rows(name, got["logits"], want, margin, vocab)
        pages += _page_rows(name, got["pages"][0], (S, conv))
        if i < 2:
            # The controls: what a wrong model reads against the TRUE
            # reference, under the same rules.
            for fault in FAULTS:
                low, _, low_S, low_conv = ref(seq, at, state_at=n - 1,
                                              fault=fault)
                controls[fault][0].extend(_rows(name, low, want, margin,
                                                vocab))
                controls[fault][1].extend(_page_rows(
                    name, (low_S, low_conv), (S, conv)))
    # doc: a question behind the cached document.
    bs = engine.block_size
    boundary = len(doc) // bs * bs
    prompt = np.concatenate([doc, rng.integers(
        0, vocab, size=min(DOC_QUESTION, engine.max_len // 16),
        dtype=np.int32)])
    got = _through_the_cache(engine, prompt, DOC_STEPS, more, TAIL_STEPS)
    toks, info = got["tokens"], got["info"]
    page0, page1, page2, page3 = got["pages"]
    # (the token an iteration consumes is the one the iteration before
    # emitted: ``more`` updates from page1 to page2)
    carried = carried_by(page1, page2, np.concatenate([prompt, toks]),
                         len(prompt) + DOC_STEPS, more)
    seq = np.concatenate([prompt, toks[:-1]])
    n = len(prompt)
    at = [n - 1 + i for i in range(1 + DOC_STEPS)] \
        + [n - 1 + DOC_STEPS + more + 1 + i for i in range(TAIL_STEPS)]
    doc_width = -(-(len(seq) + 1) // 512) * 512
    ref_doc = _reference(engine, sizes, doc_width, n_out)
    want, margin, S0, conv0 = ref_doc(seq, at, state_at=at[0])
    _, _, S3, conv3 = ref_doc(seq, at, state_at=at[-1])
    rows += _rows("doc", got["logits"], want, margin, vocab)
    pages += _page_rows("doc_prefill", page0, (S0, conv0))
    pages += _page_rows("doc_last", page3, (S3, conv3))
    # What a stream that resumed WITHOUT its snapshot would have computed.
    low, _, low_S, low_conv = ref_doc(seq, at, state_at=at[0],
                                      zero_state_at=boundary)
    controls["state_zeroed"][0].extend(_rows("doc", low, want, margin,
                                             vocab))
    controls["state_zeroed"][1].extend(_page_rows(
        "doc_prefill", (low_S, low_conv), (S0, conv0)))
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
             "chunks": info.get("chunks"), "doc_tokens": len(doc)}
    return rows, pages, controls, facts


def logits_agree(rows) -> bool:
    """Rule 1 of the module docstring over ``[(name, error, margin)]``."""
    if not rows:
        return False
    errs = np.array([e for _, e, _ in rows])
    decided = np.array([m >= MARGIN_EPS for _, _, m in rows])
    return bool(np.median(errs) <= MEDIAN_ATOL
                and (errs[decided] <= LOGIT_ATOL).all()
                and (errs <= LOGIT_ATOL).mean() >= CLEAN_MIN
                and errs.max() <= FLIP_ATOL)


def pages_agree(pages) -> bool:
    """Rule 2 over ``[(stream.layer, state error, filter rows' error)]``."""
    if not pages:
        return False
    by_layer = {}
    for name, s, c in pages:
        by_layer.setdefault(name.rsplit(".", 1)[1], []).append(max(s, c))
    return all(np.median(v) <= PAGE_RTOL and max(v) <= PAGE_FLIP_RTOL
               for v in by_layer.values())


def tokens_agree(gap) -> bool:
    return bool(len(gap) and gap.max() <= TOKEN_GAP_MAX
                and (gap > TOKEN_GAP).mean() <= TOKEN_SHARE)


def summary(rows, pages) -> dict:
    """What the two rules count, for the ``phase: serve`` line."""
    groups, layers = {}, {}
    for name, err, _ in rows:
        groups.setdefault(name.rstrip("0123456789.").split(".")[0],
                          []).append(err)
    for name, s, c in pages:
        layers.setdefault(int(name.rsplit(".", 1)[1]), []).append((s, c))
    errs = np.array([e for _, e, _ in rows]) if rows else np.zeros(0)
    decided = np.array([m >= MARGIN_EPS for _, _, m in rows], bool)
    return {"logit_max_by_group": {g: max(v) for g, v in groups.items()},
            "logit_min": float(errs.min()) if rows else None,
            "logit_median": float(np.median(errs)) if rows else None,
            "logit_decided": int(decided.sum()),
            "logit_decided_max": float(errs[decided].max())
            if decided.any() else None,
            "logit_clean_share": float((errs <= LOGIT_ATOL).mean())
            if rows else None,
            "state_median_by_layer": [float(np.median(
                [s for s, _ in layers[l]])) for l in sorted(layers)],
            "state_max_by_layer": [max(s for s, _ in layers[l])
                                   for l in sorted(layers)],
            "filter_rows_max_by_layer": [max(c for _, c in layers[l])
                                         for l in sorted(layers)]}


def pick_served(reqs, doc_of, width: int):
    """The two requests whose every emitted token is checked: the
    latest-started FINISHED one behind a document and the latest-started
    finished one that shares nothing, of those that fit the reference's
    row."""
    done = sorted((r for r in reqs if r.t_first is not None
                   and len(r.out_tokens) >= r.max_new_tokens
                   and len(r.prompt) + len(r.out_tokens) <= width),
                  key=lambda r: r.t_first)
    latest = {}
    for r in done:
        latest[doc_of[r.rid] >= 0] = r
    return list(latest.values())


def resumable(engine, prompts) -> list:
    """Whether each of ``prompts`` (a shared prefix) would be resumed at its
    last block boundary, in every class (a following token stands in for
    what a request adds)."""
    bs = engine.block_size
    return [engine.prefix_match_tokens(np.concatenate([p, [0]]))
            == len(p) // bs * bs for p in prompts]


def run(ctx):
    tr = ctx.traffic
    sizes = reference_sizes(ctx.config)
    vocab = int(ctx.config["vocab_size"])
    cfg, engine = build_engine(ctx)
    ctx.mark("weights_and_engine")
    serve_runner.warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)

    docs = longgen_traffic.documents(tr, ctx.seed, vocab)
    system = longgen_traffic.system_prompts(tr, ctx.seed, vocab)
    shared = list(system) + docs
    engine.serve(serve_runner._requests([
        {"rid": -100 - i, "prompt": p, "max_new_tokens": 1, "arrival_s": 0.0}
        for i, p in enumerate(shared)]))
    cached_before = resumable(engine, shared)
    ctx.mark("documents")

    rows, pages, controls, facts = check_against_reference(
        engine, sizes, docs[0], vocab, ctx.seed)
    ctx.mark("reference")
    engine.reset_serving_stats()
    items = longgen_traffic.requests(tr, ctx.seed, ctx.seconds, vocab, docs,
                                     system)
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], backlog=tr["backlog"],
            system_prompts=len(system), documents=len(docs),
            document_tokens=int(sum(len(d) for d in docs)),
            behind_a_document=int(sum(r["doc"] >= 0 for r in items)),
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
    cached_after = resumable(engine, shared)

    s = serve_runner.summarize(reqs, wall)
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "cache_classes", "state", "model_counters")}
    classes1 = _class_state(engine)
    totals1 = engine.allocator.snapshot_totals()
    by_class = {}
    for name, st in classes1.items():
        seen = [row[name] for row in live_by_class if name in row]
        later = seen[len(seen) // 2:]
        by_class[name] = {
            "num_blocks": st["blocks"],
            "live_blocks_mean": float(np.mean([r["live"] for r in later]))
            if later else None,
            "live_blocks_max": max((r["live"] for r in seen), default=None),
            "key_rows_mean": float(np.mean([r["key_rows"] for r in later]))
            if later else None,
            "reclaimed_in_window":
                st["reclaimed"] - classes0[name]["reclaimed"]}
    half = live[len(live) // 2:]
    kv = {"num_blocks": int(sum(st["blocks"] for st in classes1.values())),
          "block_bytes": {sp.name: sp.block_nbytes()
                          for sp in engine.cache_specs},
          "live_blocks_mean": float(np.mean(half)) if half else None,
          "live_blocks_max": max(live, default=None),
          "live_blocks_by_second": live, "classes": by_class,
          "shared_cached_before": int(sum(cached_before)),
          "shared_cached_after": int(sum(cached_after))}
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

    # The teacher-forced rows are float32 and up to SERVED_WIDTH wide: the
    # pools have done their work and make room for them.
    engine.cache.clear()
    width = min(SERVED_WIDTH, -(-int(tr["max_total"]) // 128) * 128)
    gaps_of = _token_gaps(engine, sizes, width, int(tr["output_len"]["max"]))
    served, wrong = [], 0
    for r in pick_served(reqs, {r["rid"]: r["doc"] for r in items}, width):
        gap = gaps_of(r.prompt, r.out_tokens)
        wrong += not tokens_agree(gap)
        served.append((r.rid, len(r.prompt), len(r.out_tokens),
                       float(gap.max()), float((gap > TOKEN_GAP).mean())))
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
           for name in FAULTS}}
    b = facts["boundary"]
    resumed = facts["resumed_at"] == b \
        and set((facts["cached_by_class"] or {}).values()) == {b}
    correct = s["failed"] == 0 and wrong == 0 and len(served) == 2 \
        and agree and pages_ok and state_ok \
        and all(controls_fail.values()) and resumed \
        and all(cached_before) and all(cached_after) \
        and compiles_window == 0 and s["output_tokens"] > 0
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
            limits={"median": MEDIAN_ATOL, "logit": LOGIT_ATOL,
                    "flip": FLIP_ATOL, "margin": MARGIN_EPS,
                    "clean_min": CLEAN_MIN, "page_rtol": PAGE_RTOL,
                    "page_flip_rtol": PAGE_FLIP_RTOL,
                    "state_rtol": STATE_RTOL, "chunk_rtol": CHUNK_RTOL,
                    "steps_rtol": STEPS_RTOL, "token_gap": TOKEN_GAP,
                    "token_share": TOKEN_SHARE,
                    "token_gap_max": TOKEN_GAP_MAX},
            served_tokens_checked=served, paged_kernel=engine.paged_kernel,
            max_slots=engine.max_slots, prefill_chunk=engine.prefill_chunk,
            kv=kv, memory_peak_bytes_at_window_end=peak_window,
            param_bytes=engine.param_bytes,
            offered_tokens_per_s=sum(r.max_new_tokens for r in reqs)
            / ctx.seconds, snapshot=snapshot, **s)

    record = {
        "kind": "serve", "correct": correct, "attempted": s["attempted"],
        "failed": s["failed"] + wrong,
        "end_to_end": {"serve_tokens_per_s": s["tokens_per_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "summary": s, "snapshot": snapshot, "kv": kv, "sessions": window,
        "kda": {"num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
                "layers_run": cfg.num_kda_layers,
                "moe_layers": cfg.num_hidden_layers - cfg.num_dense_layers,
                "experts_held": cfg.held[1]},
        "latent": dict({k: ctx.config[k] for k in LATENT_KEYS},
                       num_hidden_layers=cfg.num_latent_layers),
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

"""kind: agent_sessions -- long-running agent and document-workbench sessions
whose contexts start at tens to hundreds of thousands of tokens and grow turn
by turn, every turn re-sending its whole context, through
``InferenceEngine.serve``, for a configuration of the ``solar_open2`` family:
three Kimi-Delta-Attention layers (a gated DELTA rule over a float32 state a
stream, a write strength that reaches 2) to one gated NoPE grouped-query layer
that keeps K/V PAGES, over expert layers that hold a share of their experts —
two KINDS of cache in different layers, one manager, one prefix rule.

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile the prefill chunk and the decode
step; EVERY SESSION'S STARTING CONTEXT SERVED ONCE (1 new token) through
``engine.serve`` so that its K/V pages and its state snapshot at its last
block boundary sit in the prefix cache (a hit needs both kinds); the streams
of the reference comparison served through the engine's own admission,
prefill and decode, their logits and pages kept on the host (the resumed ones
build the page copy), and the longest session's K/V pages held to the
reference's rows; ``reset_serving_stats()``.  Window: arrivals over ``[0,
--seconds)`` at the traffic file's fixed rate, above what the system
sustains, cut by the scheduler at the window's end; every request is the next
turn of a session (``lib/sessions_traffic.py``): a hit ACROSS KINDS at the end
of the turn before, a prefill of what the turn adds (the chunk that reaches
the new boundary leaves a snapshot), then decode over the whole context.
After the window the pools are dropped (a float32 forward over 37k positions
does not fit beside them) and the reference runs: the kept logits and pages
against it, its wrong models against it, and every emitted token of two
finished requests served inside the full batch.

``correct`` (decided on the chip at the published widths, from what the timed
path produced; logits and pages, not tokens), every part of it:
1. logits through BOTH kinds of cache against the reference's full forward
   (``lib/solar_open2_reference.py``: the delta rule token by token) in three
   groups: ``short``: the prefill and first decode of ``N_SHORT`` unshared
   prompts of ``SHORT_LEN`` tokens (two chunk programs); ``near``: a turn of
   ``NEAR_MESSAGE`` tokens RIGHT BEHIND the snapshot boundary of the first
   session of ``LONG_CONTEXT`` + tokens (it must have resumed there in both
   classes: pages by reference + the snapshot copied), its prefill and
   ``NEAR_STEPS`` decode iterations; ``turn``: that session's next turn with
   a message of the traffic's median length, its prefill and ``TURN_STEPS``
   iterations, against the reference over the whole sequence from position
   0.  Four expert layers of top-8-of-320 flip near-ties, and a flip travels
   on through the state, so (``logits_agree``): the MEDIAN error within
   ``MEDIAN_ATOL``; every position whose routing the reference finds DECIDED
   (margin >= ``MARGIN_EPS``) within ``LOGIT_ATOL``; at least ``CLEAN_MIN``
   of all positions within ``LOGIT_ATOL``; none over ``FLIP_ATOL``;
2. the state PAGES of those streams (after prefill; the ``turn`` stream after
   its last iteration) against the reference's ``S_t`` and filter rows, a KDA
   layer each, by relative error (Frobenius): their MEDIAN over streams
   within ``PAGE_RTOL`` a layer and none over ``PAGE_FLIP_RTOL``
   (``pages_agree``) — what holds the RESUMED path: a page is what a snapshot
   carries;
3. at the LONGEST session (160k+ tokens) the K/V PAGES of layer 0 — the
   grouped-query layer: its rows are functions of the embeddings alone —
   against the reference's rows, every block the stream's table names, by
   relative error a block: none over ``KV_RTOL``.  (The served layer-0 attend
   over those rows is not compared on its own: the programs hand out no
   layer's output, and the ``turn`` group's logits read it through 36k
   rows.);
4. the comparison can fail, shown every run on the same positions, the
   reference's wrong models read against the true reference under the same
   rules, and EACH must fail at least one of the rules it is read under
   (which ones is on the ``phase: serve`` line, ``controls_fail_by_rule``):
   ``beta = sigmoid(.)`` (no 2) and no attention gate under 1 on the
   ``short`` prompts; the reference in 8-bit (e4m3) operands under 1 and 2 on
   ``short`` prompts and its rows under 3; the state ZEROED at the resume
   boundary under 1 and 2 on the ``near`` stream.  A state carried in
   bfloat16 moves logits and pages LESS than the served path's bf16
   activations do (read under rules 1 and 2 and reported:
   ``bf16_state_read_as``), so the state's own precision is held apart, as a
   test of STORAGE: of every served page's float32 state entries, the share
   whose low 16 mantissa bits are not all zero is at least
   ``LOW_BITS_SHARE`` (a float32 state: nearly all; one rounded to bfloat16
   after every token: none — the reference's own such state must fail it);
5. every emitted token of two FINISHED requests served inside the full batch
   (the latest-started ones that fit ``SERVED_WIDTH``: turns of the shortest
   sessions) against the reference's teacher-forced forward: none further
   below its largest logit than ``TOKEN_GAP_MAX`` and at most ``TOKEN_SHARE``
   of a request's tokens further than ``TOKEN_GAP`` (``tokens_agree``);
6. every session resumable at its boundary in both classes when the window
   opens, no request over its length, zero compiles in the window, some
   output.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import solar_open2 as solar_model   # fails at once
#             on a program that has no such family: nothing has run yet
from perfbench.lib import solar_open2_costs as costs
from perfbench.lib import solar_open2_reference as reference
from perfbench.lib import sessions_traffic, traffic as traffic_lib, xplane
from perfbench.runners import _common, serve as serve_runner
from perfbench.runners.chat_state import _page_rows
from perfbench.runners.longgen import _page, resumable   # a KDA stream's page
#             as float32 (state, filter rows); a context still resumable at
#             its last block boundary in every class
from perfbench.runners.mixed_docqa import _class_state, measure

# Served logits (bf16 weights, activations, K/V and filter rows; fp32 state,
# gates, norms, softmax, routing, the triangular solve and accumulation)
# against the float32 reference on the same bf16 weights upcast.  Logits of
# the seeded model have a standard deviation of about 1
# (``solar_open2_init``).  The limits and the chip readings they stand
# between are in PERF.md section 2 (PR 64), each with its run.
MEDIAN_ATOL = 0.2
LOGIT_ATOL = 0.4
FLIP_ATOL = 1.0
MARGIN_EPS = 0.012
CLEAN_MIN = 0.75
PAGE_RTOL = 0.1
PAGE_FLIP_RTOL = 0.5
KV_RTOL = 2.0 ** -7
LOW_BITS_SHARE = 0.5
TOKEN_GAP = 1.0
TOKEN_SHARE = 0.10
TOKEN_GAP_MAX = 8.0
N_SHORT = 6
SHORT_LEN = 600
LONG_CONTEXT = 32768
NEAR_MESSAGE = 2
NEAR_STEPS = 5
TURN_STEPS = 5
WIDTH = 1024               # one padded row for the short prompts
SERVED_WIDTH = 32768       # ... and for the teacher-forced rows
KV_BLOCKS = 64             # table entries a step of the K/V comparison
Q_BLOCK = 64
SPANS = serve_runner.SPANS
FAULTS = ("no_two", "no_gate", "bf16_state")


def model_config(sizes: dict):
    """The program's SolarOpen2Config from the configuration file: the
    published keys as published; the router's width is the PUBLISHED expert
    count, ``held`` the file's ``n_routed_experts``."""
    return solar_model.SolarOpen2Config.from_hf(
        sizes, n_routed_experts=int(sizes["n_routed_experts_published"]),
        held=(0, int(sizes["n_routed_experts"])),
        **({"dtype": jnp.dtype(sizes["assumed"]["compute_dtype"])}
           if "compute_dtype" in sizes.get("assumed", {}) else {}))


def reference_sizes(sizes: dict) -> dict:
    """The configuration file's dict as the reference reads it."""
    return dict(sizes, held=(0, int(sizes["n_routed_experts"])))


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: solar_model.solar_open2_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def _reference(engine, sizes, width: int, n_out: int, cast=None):
    """One compiled reference for token rows padded to ``width`` (causal:
    padding after the real tokens changes nothing before it) and ``n_out``
    output positions: (logits, routing margins, states, filter rows at
    ``state_at``); ``zero_state_at`` and ``fault`` are traced (0 / None: the
    true model), so the wrong models cost no program of their own; ``cast``
    (8-bit operands) is a program of its own."""
    fn = jax.jit(lambda p, t, out, at, cut, fault: reference.forward(
        p, t, sizes, out_positions=out, q_block=Q_BLOCK, fault=fault,
        state_at=at, zero_state_at=cut, cast=cast))

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
        h, _, _ = reference.hidden(p, t, sizes, q_block=Q_BLOCK)
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


def _through_the_cache(engine, prompt, steps: int, kv_check=None):
    """``prompt`` served alone through the engine's own admission, prefill
    and decode, ``steps`` iterations with logits.  Returns a dict:
    ``tokens`` emitted, ``logits`` of the prefill and of the iterations,
    admission ``info``, ``pages`` after the prefill and after the last
    iteration, and ``kv``: what ``kv_check(slot)`` returns while the stream
    holds its slot."""
    slot = engine.select_slot(prompt, 1 + steps)
    tok, pre = engine.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=1 + steps)
    info = dict(engine.last_admit_info(slot))
    pages = [_page(engine, slot)]
    kv = kv_check(slot) if kv_check else None
    engine.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre, np.float32)]
    for _ in range(steps):
        sampled, dec = engine.decode_once(return_logits=True)
        got.append(np.asarray(dec[slot], np.float32))
        toks.append(int(sampled[slot]))
    pages.append(_page(engine, slot) if steps else pages[-1])
    engine.release_slot(slot)
    return {"tokens": toks, "logits": np.stack(got), "info": info,
            "pages": pages, "kv": kv}


def kv_pages_against_rows(engine, sizes, tokens):
    """-> ``check(slot)``: layer 0's K and V pages of the stream in ``slot``,
    every block of ``tokens`` its table names, against the reference's rows
    (``reference.kv_rows``), ``KV_BLOCKS`` table entries a step: [(first
    token, relative error of K, of V, the same of the reference's rows held
    in e4m3: the control)]."""
    from deepspeed_tpu.inference import kv_cache
    cfg = engine.model_cfg
    bs, D = engine.block_size, cfg.head_dim
    names = engine.cache_specs[0].pool_names

    def rel(a, b):
        return jnp.sqrt(jnp.square(a - b).sum() / jnp.square(b).sum())

    @jax.jit
    def errors(params, kpool, vpool, g, ids, toks):
        want = reference.kv_rows(params, toks, sizes)
        out = []
        for pool, rows in zip((kpool, vpool), want):
            held = kv_cache.paged_layer_view(pool, 0, D)[g, ids]
            got = jnp.moveaxis(held, 1, 2).reshape(rows.shape)  # [T, nKV, D]
            out += [rel(got.astype(jnp.float32), rows),
                    rel(reference._rounded(rows, jnp.float8_e4m3fn), rows)]
        return jnp.stack(out)

    def check(slot):
        table = np.asarray(engine.block_tables[slot][:-1])
        n = len(tokens) // bs                      # whole blocks of tokens
        assert (table[:n] >= 0).all(), "the stream's table lacks a block"
        g, out, step = engine.group_of(slot), [], min(KV_BLOCKS, n)
        # (one compiled shape: the last step may lap the one before)
        for first in sorted({*range(0, n - step + 1, step), n - step}):
            ids = table[first:first + step]
            toks = tokens[first * bs:(first + step) * bs]
            k, k8, v, v8 = (float(e) for e in errors(
                engine._params, engine.cache[names[0]],
                engine.cache[names[1]], g, jnp.asarray(ids),
                jnp.asarray(toks)))
            out.append((first * bs, k, v, k8, v8))
        return out
    return check


def served_streams(engine, hist, vocab: int, seed: int, message_len: int,
                   sizes):
    """The streams of rules 1-3 served through the engine, in set-up: a dict
    of what they produced (host arrays) for ``against_reference``."""
    rng = np.random.default_rng([seed, 5])
    short = min(SHORT_LEN, engine.max_len // 4)
    out = {"short": [], "short_len": short}
    for _ in range(N_SHORT):
        prompt = rng.integers(0, vocab, size=short, dtype=np.int32)
        out["short"].append((prompt, _through_the_cache(engine, prompt, 1)))
    bs = engine.block_size
    rank = next((i for i, h in enumerate(hist)
                 if len(h) >= min(LONG_CONTEXT, engine.max_len // 2)),
                len(hist) - 1)
    history = hist[rank]
    boundary = len(history) // bs * bs       # where set-up's snapshot is
    near = np.concatenate([history[:boundary], rng.integers(
        0, vocab, size=NEAR_MESSAGE, dtype=np.int32)])
    out["near"] = (near, _through_the_cache(engine, near, NEAR_STEPS))
    turn = np.concatenate([history, rng.integers(
        0, vocab, size=message_len, dtype=np.int32)])
    out["turn"] = (turn, _through_the_cache(engine, turn, TURN_STEPS))
    # the longest session, resumed at its boundary: its table names every
    # block its context holds
    longest = hist[-1]
    probe = np.concatenate([longest, rng.integers(0, vocab, size=NEAR_MESSAGE,
                                                  dtype=np.int32)])
    out["longest"] = _through_the_cache(
        engine, probe, 0,
        kv_check=kv_pages_against_rows(engine, sizes, longest))
    out.update(session=rank, boundary=boundary, context_tokens=len(history),
               longest_tokens=len(longest))
    return out


def low_bits_share(state) -> float:
    """Of float32 ``state``'s entries, the share whose low 16 mantissa bits
    are not all zero: what a value held in bfloat16 cannot have."""
    bits = np.ascontiguousarray(state, np.float32).view(np.uint32)
    return float(((bits & 0xFFFF) != 0).mean())


def _rows(name, got, want, margin, vocab):
    return [(f"{name}.{j}", float(np.abs(got[j, :vocab]
                                         - want[j, :vocab]).max()),
             float(margin[j])) for j in range(len(got))]


def against_reference(engine, sizes, served, vocab: int):
    """(logit rows [(group.what, |logit error| max, routing margin)], page
    rows [(group.what.layer, state error, filter rows' error)], {control:
    (logit rows, page rows)}, facts) of what ``served_streams`` kept."""
    short = served["short_len"]
    ref = _reference(engine, sizes, min(WIDTH, engine.max_len), 2)
    ref_8bit = _reference(engine, sizes, min(WIDTH, engine.max_len), 2,
                          cast=jnp.float8_e4m3fn)
    rows, pages = [], []
    controls = {name: ([], []) for name in FAULTS + ("e4m3",
                                                     "state_zeroed")}
    low_bits = {"served": [], "bf16_state": []}
    for i, (prompt, got) in enumerate(served["short"]):
        name = f"short{i}"
        seq, at = np.concatenate([prompt, got["tokens"][:1]]), \
            [short - 1, short]
        want, margin, S, conv = ref(seq, at, state_at=short - 1)
        rows += _rows(name, got["logits"], want, margin, vocab)
        pages += _page_rows(name, got["pages"][0], (S, conv))
        low_bits["served"].append(low_bits_share(got["pages"][0][0]))
        if i < 2:
            # The controls: what a wrong model reads against the TRUE
            # reference, under the same rules.
            for fault, fn in [(f, ref) for f in FAULTS] + [(None, ref_8bit)]:
                low, _, low_S, low_conv = fn(seq, at, state_at=short - 1,
                                             fault=fault)
                c = controls[fault or "e4m3"]
                c[0].extend(_rows(name, low, want, margin, vocab))
                c[1].extend(_page_rows(name, (low_S, low_conv), (S, conv)))
                if fault == "bf16_state":
                    low_bits["bf16_state"].append(low_bits_share(low_S))
    near, got_near = served["near"]
    turn, got_turn = served["turn"]
    steps = max(NEAR_STEPS, TURN_STEPS)
    ref_long = _reference(engine, sizes,
                          -(-(len(turn) + steps + 1) // 512) * 512,
                          1 + steps)
    # near: every checked position is one the resumed state reaches
    seq = np.concatenate([near, got_near["tokens"][:-1]])
    at = [len(near) - 1 + i for i in range(1 + NEAR_STEPS)]
    want, margin, S, conv = ref_long(seq, at, state_at=at[0])
    rows += _rows("near", got_near["logits"], want, margin, vocab)
    pages += _page_rows("near", got_near["pages"][0], (S, conv))
    low_bits["served"] += [low_bits_share(got_near["pages"][0][0]),
                           low_bits_share(got_turn["pages"][1][0])]
    # What a stream that resumed WITHOUT its snapshot would have computed.
    low, _, low_S, low_conv = ref_long(seq, at, state_at=at[0],
                                       zero_state_at=served["boundary"])
    controls["state_zeroed"][0].extend(_rows("near", low, want, margin,
                                             vocab))
    controls["state_zeroed"][1].extend(_page_rows(
        "near", (low_S, low_conv), (S, conv)))
    # turn: the session's next turn as the window's are.
    seq = np.concatenate([turn, got_turn["tokens"][:-1]])
    at = [len(turn) - 1 + i for i in range(1 + TURN_STEPS)]
    want, margin, S, conv = ref_long(seq, at, state_at=at[-1])
    rows += _rows("turn", got_turn["logits"], want, margin, vocab)
    pages += _page_rows("turn", got_turn["pages"][1], (S, conv))
    info_n, info_t, info_l = (got_near["info"], got_turn["info"],
                              served["longest"]["info"])
    facts = {"session": served["session"],
             "context_tokens": served["context_tokens"],
             "boundary": served["boundary"],
             "near_resumed_at": info_n.get("cached_tokens", 0),
             "near_cached_by_class": info_n.get("cached_by_class"),
             "turn_resumed_at": info_t.get("cached_tokens", 0),
             "turn_cached_by_class": info_t.get("cached_by_class"),
             "turn_lost_to_kind_tokens": info_t.get("lost_to_kind_tokens"),
             "turn_chunks": info_t.get("chunks"),
             "longest_tokens": served["longest_tokens"],
             "longest_resumed_at": info_l.get("cached_tokens", 0),
             "state_low_bits_share_min": min(low_bits["served"]),
             "bf16_state_low_bits_share_max": max(low_bits["bf16_state"])}
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


def kv_agree(kv, served: bool = True) -> bool:
    """Rule 3 over ``[(first token, K error, V error, the e4m3 rows' K and
    V errors)]``: the served pages' (or the control's) errors."""
    at = (1, 2) if served else (3, 4)
    return bool(kv) and max(max(r[i] for i in at) for r in kv) <= KV_RTOL


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


def pick_served(reqs, width: int, n: int = 2):
    """The requests whose every emitted token is checked: the ``n``
    latest-started FINISHED ones that fit the reference's row."""
    done = sorted((r for r in reqs if r.t_first is not None
                   and len(r.out_tokens) >= r.max_new_tokens
                   and len(r.prompt) + len(r.out_tokens) <= width),
                  key=lambda r: -r.t_first)
    return done[:n]


def run(ctx):
    tr = ctx.traffic
    sizes = reference_sizes(ctx.config)
    vocab = int(ctx.config["vocab_size"])
    cfg, engine = build_engine(ctx)
    bs = engine.block_size
    ctx.mark("weights_and_engine")
    serve_runner.warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)

    hist = sessions_traffic.histories(tr, ctx.seed, vocab)
    engine.serve(serve_runner._requests([
        {"rid": -100 - i, "prompt": h, "max_new_tokens": 1, "arrival_s": 0.0}
        for i, h in enumerate(hist)]))
    ctx.mark("sessions")
    served_ref = served_streams(engine, hist, vocab, ctx.seed,
                                int(tr["message_len"]["median"]), sizes)
    cached_before = resumable(engine, hist)
    ctx.mark("served_for_reference")
    engine.reset_serving_stats()
    items = sessions_traffic.requests(tr, ctx.seed, ctx.seconds, vocab, hist)
    page_bytes = engine.cache_specs[-1].block_nbytes()
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], sessions=len(hist),
            context_tokens=int(sum(len(h) for h in hist)),
            context_blocks=int(sum(-(-len(h) // bs) for h in hist)),
            turns_max=max(r["turn"] for r in items) + 1,
            state_page_bytes=page_bytes,
            state_page_tokens=engine.cache_specs[-1].page_tokens,
            snapshot_worth_tokens=costs.snapshot_worth_tokens(
                ctx.config, cfg.num_kda_layers, cfg.num_gqa_layers))

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
    cached_after = resumable(engine, hist)

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
          "sessions_cached_before": int(sum(cached_before)),
          "sessions_cached_after": int(sum(cached_after))}
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

    # The reference's rows are float32 and up to 37k wide: the pools have
    # done their work and make room for them.
    engine.cache.clear()
    rows, pages, controls, facts = against_reference(engine, sizes,
                                                     served_ref, vocab)
    width = min(SERVED_WIDTH, -(-int(tr["max_total"]) // 128) * 128)
    gaps_of = _token_gaps(engine, sizes, width, int(tr["output_len"]["max"]))
    served, wrong = [], 0
    for r in pick_served(reqs, width):
        gap = gaps_of(r.prompt, r.out_tokens)
        wrong += not tokens_agree(gap)
        served.append((r.rid, len(r.prompt), len(r.out_tokens),
                       float(gap.max()), float((gap > TOKEN_GAP).mean())))
    kv_rows = served_ref["longest"]["kv"]
    agree, kv_ok = logits_agree(rows), kv_agree(kv_rows)
    pages_ok = pages_agree(pages) \
        and facts["state_low_bits_share_min"] >= LOW_BITS_SHARE
    # The controls have to fail the comparison the system has to pass, on the
    # same positions: each wrong model at least one of the rules it is read
    # under (which ones is on the ``phase: serve`` line).
    by_rule = {
        "no_two.logits": not logits_agree(controls["no_two"][0]),
        "no_gate.logits": not logits_agree(controls["no_gate"][0]),
        "e4m3.logits": not logits_agree(controls["e4m3"][0]),
        "e4m3.pages": not pages_agree(controls["e4m3"][1]),
        "e4m3.kv_rows": not kv_agree(kv_rows, served=False),
        "state_zeroed.logits": not logits_agree(controls["state_zeroed"][0]),
        "state_zeroed.pages": not pages_agree(controls["state_zeroed"][1]),
        "bf16_state.low_bits":
            facts["bf16_state_low_bits_share_max"] < LOW_BITS_SHARE}
    controls_fail = {name: any(v for k, v in by_rule.items()
                               if k.startswith(name + "."))
                     for name in ("no_two", "no_gate", "e4m3",
                                  "state_zeroed", "bf16_state")}
    b = facts["boundary"]
    resumed = all(
        facts[k + "_resumed_at"] == b and set(
            (facts[k + "_cached_by_class"] or {}).values()) == {b}
        for k in ("near", "turn")) \
        and facts["longest_resumed_at"] == facts["longest_tokens"] // bs * bs
    correct = s["failed"] == 0 and wrong == 0 and len(served) == 2 \
        and agree and pages_ok and kv_ok \
        and all(controls_fail.values()) and resumed \
        and all(cached_before) and compiles_window == 0 \
        and s["output_tokens"] > 0
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, logits_agree=agree, page_checks=pages,
            pages_agree=pages_ok, kv_checks=kv_rows, kv_agree=kv_ok,
            summary=summary(rows, pages),
            controls={name: summary(lg, pg)
                      for name, (lg, pg) in controls.items()},
            controls_fail=controls_fail, controls_fail_by_rule=by_rule,
            bf16_state_read_as={
                "logits_agree": logits_agree(controls["bf16_state"][0]),
                "pages_agree": pages_agree(controls["bf16_state"][1])},
            facts=facts, resumed=resumed, window=window,
            limits={"median": MEDIAN_ATOL, "logit": LOGIT_ATOL,
                    "flip": FLIP_ATOL, "margin": MARGIN_EPS,
                    "clean_min": CLEAN_MIN, "page_rtol": PAGE_RTOL,
                    "page_flip_rtol": PAGE_FLIP_RTOL, "kv_rtol": KV_RTOL,
                    "low_bits_share": LOW_BITS_SHARE,
                    "token_gap": TOKEN_GAP, "token_share": TOKEN_SHARE,
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
        **costs.record_sizes(ctx.config, {
            "kda": cfg.num_kda_layers, "gqa": cfg.num_gqa_layers,
            "moe": cfg.num_moe_layers, "experts_held": cfg.held[1]}),
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

"""kind: sessions -- conversations that grow turn by turn, every turn
re-sending its whole history, through ``InferenceEngine.serve``, for a
configuration of the ``lfm2_moe`` family: gated short-convolution layers
that keep a fixed state a stream BESIDE grouped-query attention layers that
keep K/V pages, two KINDS of cache in one manager with one prefix rule,
every expert held.

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile the prefill chunk, the decode step
and the page copy; EVERY SESSION'S HISTORY SERVED ONCE (1 new token)
through ``engine.serve`` so that its pages and its snapshot sit in the
prefix cache; the float32 reference comparison and its controls;
``reset_serving_stats()``.  Window: arrivals over ``[0, --seconds)`` at the
traffic file's fixed rate, above what the system sustains, cut by the
scheduler at the window's end; every request is the next turn of a session
(``lib/sessions_traffic.py``): a hit ACROSS KINDS at the end of the turn
before (pages shared by reference + a conv snapshot copied at the same
boundary), a prefill of what the turn adds, a new snapshot and new cached
blocks, then decode.  After the window: emitted tokens of finished requests
served inside the full batch against the reference.

``correct`` (decided on the chip at the published widths, from what the
timed path produced; logits and pages, not tokens), every part of it:
1. logits through BOTH kinds of cache against the reference's full forward
   (``lib/lfm2_reference.py``) in THREE GROUPS of positions: ``short``: the
   prefill and first decode of ``N_SHORT`` unshared prompts of
   ``SHORT_LEN`` tokens; ``near``: a turn of ``NEAR_MESSAGE`` tokens RIGHT
   BEHIND the snapshot boundary of the first session whose history is 10k+
   tokens (it must have resumed there in both classes): its prefill and
   ``NEAR_STEPS`` decode iterations — the positions a conv state reaches;
   ``turn``: that session's next turn with a message of the traffic's
   median length, its prefill and ``TURN_STEPS`` iterations, against the
   reference over the whole sequence from position 0.  The rule
   (``logits_agree``; why below): at least ``CLEAN_MIN`` of all positions
   within ``LOGIT_ATOL``, and none over ``FLIP_ATOL``;
2. the conv PAGES of the ``short`` and ``near`` streams (after prefill)
   and of the ``turn`` stream (after its last iteration) against the
   reference's ``(z_{t-1}, z_t)``, every conv layer, by relative error
   (Frobenius; ``pages_agree``): conv layer 0, which no expert layer
   precedes, within ``PAGE_RTOL`` for every stream; every conv layer's
   MEDIAN over the streams within ``PAGE_MEDIAN_RTOL``; none over
   ``PAGE_FLIP_RTOL``.  This is the comparison that holds the RESUMED path:
   a page is what a snapshot carries, and a routing flip moves it by a
   fifth where a lost state moves it by more than the whole;
3. the comparison can fail, shown every run: the reference with the conv
   state ZEROED at the resume boundary must fail 1 and 2 on the ``near``
   stream, and the reference in 8-bit (e4m3) operands must fail 1 and 2 on
   ``short`` streams;
4. every emitted token of FINISHED requests served inside the full batch,
   the latest-started first, whose prompt + reply fit ``SERVED_WIDTH``,
   until ``SERVED_TOKENS`` tokens are checked, within ``TOKEN_GAP`` of the
   reference's largest logit in its teacher-forced forward;
5. every history still resumable at its last block when the window opens,
   no request over its length, zero compiles in the window, some output.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import lfm2 as lfm2_model     # fails at once on a
#                     program that has no such family: nothing has run yet
from perfbench.lib import lfm2_reference as reference
from perfbench.lib import sessions_traffic, traffic as traffic_lib, xplane
from perfbench.runners import _common, serve as serve_runner
from perfbench.runners.mixed_docqa import _class_state, measure

# Served logits (bf16 weights, activations, K/V pools and conv pages; fp32
# routing, softmax, norms, the filter's sum and accumulation) against the
# float32 reference on the same bf16 weights upcast.  Logits of the randomly
# initialised model have a standard deviation of about 0.9 (unit-RMS final
# norm x a 2048-wide tied head at std 0.02).  Read on the chip (my chip
# runs, PR 45 c1-c5; PERF.md section 2):
# - rounding, where no routing decision flipped: 0.07-0.14;
# - a flipped routing decision: top-4 of 64 in one group is discrete, and
#   over EIGHT expert layers the least gap between the 4th and 5th candidate
#   (the reference's ``margin``, in units of c = s + b) is under 0.003 at
#   two positions in three, where the served c carries noise of ~0.002 (the
#   bf16 residual stream): the served path chooses the other expert at HALF
#   the positions, which read 0.18-1.02.  And a flip does not stay at its
#   position, as it does in the attention-only cells: the flipped row's z
#   enters the next two rows of every later conv layer through the filter,
#   and the stream's own state carries it on, so the positions BEHIND a flip
#   read 0.2-0.4 for a dozen steps whatever their own margin (c1: a stream's
#   steps 5-10 flipped at 0.6-1.0, steps 11-15 read 0.18-0.41 at margins of
#   0.005-0.010).  So neither ``runners/reason.py``'s clause on DECIDED
#   positions nor a group's median carries over: a stream whose first
#   position flips has no clean position at all.  The rule that is left for
#   logits is global — a FAULT is systematic and leaves NO position clean
#   (the reference in e4m3 operands reads 1.0-1.3 at every position, a state
#   zeroed at the boundary 0.32-5.5 behind it: 0 clean of 8 each, every
#   run), a flip leaves the positions it does not reach clean: at least
#   CLEAN_MIN 8% of all positions within LOGIT_ATOL 0.2 (read 36-48%;
#   sixty-four positions of twenty-six streams), none over FLIP_ATOL 3.0
#   (three times the largest flip read).
# - What holds a PATH (the resumed one above all) is the conv pages: a
#   page's relative error reads 0.0033-0.0040 in conv layer 0 (no expert
#   layer precedes it: rounding alone; limit PAGE_RTOL 0.02, where e4m3
#   operands read 0.03-0.05), a layer's median over the streams 0.004-0.09,
#   rising with depth, and 0.22 at most after a flip (PAGE_MEDIAN_RTOL 0.25,
#   PAGE_FLIP_RTOL 0.6); a stream that resumed WITHOUT its snapshot reads
#   1.22-1.33 in every layer but the first: twice the limit, six times the
#   largest served reading.
# - an emitted token lies within twice a flip of the reference's largest
#   logit: read 0.16-0.68 over 53-113 tokens a request; a token from a wrong
#   slot or a stale state is a random one, about 4.5 below the largest of
#   65,536.  TOKEN_GAP 3.0.
LOGIT_ATOL = 0.2
FLIP_ATOL = 3.0
CLEAN_MIN = 0.08             # of all positions
PAGE_RTOL = 0.02
PAGE_MEDIAN_RTOL = 0.25
PAGE_FLIP_RTOL = 0.6
TOKEN_GAP = 3.0
SERVED_TOKENS = 300
SERVED_WIDTH = 8192
N_SHORT = 24
SHORT_LEN = 600
NEAR_MESSAGE = 2
NEAR_STEPS = 7
TURN_STEPS = 7
LONG_HISTORY = 10240
Q_BLOCK = 128
SPANS = serve_runner.SPANS
# What ``lib/afmoe_costs.py`` reads (the rooflines of the grouped-head
# attend and of the product over every expert), under its keys.
COST_KEYS = ("hidden_size", "moe_intermediate_size", "num_attention_heads",
             "num_key_value_heads", "num_experts", "num_experts_per_tok",
             "num_hidden_layers", "num_dense_layers")


def model_config(sizes: dict):
    """The program's Lfm2Config from the configuration file: the published
    keys as published (of ``layer_types`` the dense layer's entry and whole
    periods: ``Lfm2Config.from_hf``)."""
    return lfm2_model.Lfm2Config.from_hf(
        sizes, initializer_range=float(sizes["assumed"]["initializer_range"]))


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: lfm2_model.lfm2_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def _reference(engine, sizes, width: int, n_out: int, cast=None):
    """One compiled reference for token rows padded to ``width`` (causal:
    padding after the real tokens changes nothing before it) and ``n_out``
    output positions: (logits, margin, conv states at ``state_at``);
    ``zero_state_at`` is traced (0: the true model)."""
    fn = jax.jit(lambda p, t, out, at, cut: reference.forward(
        p, t, sizes, out_positions=out, q_block=Q_BLOCK, cast=cast,
        state_at=at, zero_state_at=cut))

    def run(tokens, out_positions, state_at=0, zero_state_at=0):
        row = np.zeros(width, np.int32)
        row[:len(tokens)] = tokens
        out = np.zeros(n_out, np.int32)
        out[:len(out_positions)] = out_positions
        lg, margin, states = fn(
            engine._params, jnp.asarray(row), jnp.asarray(out),
            jnp.int32(state_at), jnp.int32(zero_state_at))
        n = len(out_positions)
        return np.asarray(lg)[:n], np.asarray(margin)[:n], np.asarray(states)
    return run


def _width(n: int) -> int:
    return -(-n // 512) * 512


_take_page = jax.jit(lambda pool, g, page: pool[:, g, page].astype(
    jnp.float32))


def _page(engine, slot):
    """The stream's conv page as float32 ``[conv layers, L - 1, H]``."""
    g, page = engine.group_of(slot), int(engine.block_tables[slot][-1])
    name = engine.cache_specs[-1].pool_names[0]
    tile = np.asarray(_take_page(engine.cache[name], g, page))
    cfg = engine.model_cfg
    return tile.reshape(tile.shape[0], cfg.conv_L_cache - 1, cfg.hidden_size)


def _through_the_cache(engine, prompt, steps: int):
    """(tokens emitted, logits of the prefill and of ``steps`` decode
    iterations, admission info, the conv page after prefill, after the last
    iteration) of ``prompt`` served alone through the engine's own
    admission, prefill and decode."""
    slot = engine.select_slot(prompt, 1 + steps)
    tok, pre = engine.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=1 + steps)
    info = dict(engine.last_admit_info(slot))
    page0 = _page(engine, slot)
    engine.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre, np.float32)]
    for _ in range(steps):
        sampled, dec = engine.decode_once(return_logits=True)
        toks.append(int(sampled[slot]))
        got.append(np.asarray(dec[slot], np.float32))
    page1 = _page(engine, slot)
    engine.release_slot(slot)
    return toks, np.stack(got), info, page0, page1


def _rows(group, names, info, got, want, margin, vocab):
    return [(f"{group}.{name}", info.get("cached_tokens", 0),
             float(np.abs(got[j, :vocab] - want[j, :vocab]).max()),
             float(margin[j])) for j, name in enumerate(names)]


def _page_rows(name, page, want):
    """[(what.layer, relative error)] of a page against the reference's
    states, a conv layer each."""
    err = np.sqrt(np.square(page - want).sum((1, 2))
                  / np.maximum(np.square(want).sum((1, 2)), 1e-30))
    return [(f"{name}.{layer}", float(e)) for layer, e in enumerate(err)]


def check_against_reference(engine, sizes, hist, vocab: int, seed: int,
                            message_len: int):
    """(logit rows [(group.what, cached tokens, |logit error| max, margin)],
    page rows [(group.what.layer, relative error)], {control: (logit rows,
    page rows)}, facts about the resumed turns)."""
    rng = np.random.default_rng([seed, 3])
    short = min(SHORT_LEN, engine.max_len // 4)
    ref_short = _reference(engine, sizes, _width(short + 1), 2)
    ref_short_8bit = _reference(engine, sizes, _width(short + 1), 2,
                                cast=jnp.float8_e4m3fn)
    rows, pages = [], []
    controls = {"e4m3": ([], []), "state_zeroed": ([], [])}
    for i in range(N_SHORT):                                   # short
        prompt = rng.integers(0, vocab, size=short, dtype=np.int32)
        toks, got, info, page0, _ = _through_the_cache(engine, prompt, 1)
        seq = np.concatenate([prompt, toks[:1]])
        at = [short - 1, short]
        want, margin, state = ref_short(seq, at, state_at=short - 1)
        rows += _rows("short", [f"{i}.prefill", f"{i}.decode"], info, got,
                      want, margin, vocab)
        pages += _page_rows(f"short.{i}", page0, state)
        if i < 4:
            low, _, low_state = ref_short_8bit(seq, at, state_at=short - 1)
            controls["e4m3"][0].extend(_rows(
                "short", [f"{i}.prefill", f"{i}.decode"], info, low, want,
                margin, vocab))
            controls["e4m3"][1].extend(_page_rows(f"short.{i}", low_state,
                                                  state))
    bs = engine.block_size
    rank = next((i for i, h in enumerate(hist) if len(h) >= LONG_HISTORY),
                len(hist) - 1)
    history = hist[rank]
    boundary = len(history) // bs * bs       # where set-up's snapshot is
    steps = max(NEAR_STEPS, TURN_STEPS)
    ref_long = _reference(
        engine, sizes, _width(len(history) + message_len + steps + 1),
        1 + steps)
    # near: a message right behind the boundary; every checked position is
    # one a conv state reaches (each conv layer carries it two rows on).
    prompt = np.concatenate([history[:boundary], rng.integers(
        0, vocab, size=NEAR_MESSAGE, dtype=np.int32)])
    toks, got, near, page0, _ = _through_the_cache(engine, prompt,
                                                   NEAR_STEPS)
    seq = np.concatenate([prompt, toks[:-1]])
    at = [len(prompt) - 1 + i for i in range(1 + NEAR_STEPS)]
    names = ["prefill"] + list(range(1, 1 + NEAR_STEPS))
    want, margin, state = ref_long(seq, at, state_at=at[0])
    rows += _rows("near", names, near, got, want, margin, vocab)
    pages += _page_rows("near", page0, state)
    # What a stream that resumed WITHOUT its snapshot would have computed,
    # read against the true reference.
    low, _, low_state = ref_long(seq, at, state_at=at[0],
                                 zero_state_at=boundary)
    controls["state_zeroed"][0].extend(
        _rows("near", names, near, low, want, margin, vocab))
    controls["state_zeroed"][1].extend(_page_rows("near", low_state, state))
    # turn: the session's next turn as the window's are.
    prompt = np.concatenate([history, rng.integers(
        0, vocab, size=message_len, dtype=np.int32)])
    toks, got, turn, _, page1 = _through_the_cache(engine, prompt,
                                                   TURN_STEPS)
    seq = np.concatenate([prompt, toks[:-1]])
    at = [len(prompt) - 1 + i for i in range(1 + TURN_STEPS)]
    want, margin, state = ref_long(seq, at, state_at=at[-1])
    rows += _rows("turn", ["prefill"] + list(range(1, 1 + TURN_STEPS)), turn,
                  got, want, margin, vocab)
    pages += _page_rows("turn", page1, state)
    facts = {"session": rank, "history_tokens": len(history),
             "boundary": boundary,
             "near_resumed_at": near.get("cached_tokens", 0),
             "near_cached_by_class": near.get("cached_by_class"),
             "turn_resumed_at": turn.get("cached_tokens", 0),
             "turn_cached_by_class": turn.get("cached_by_class"),
             "turn_lost_to_kind_tokens": turn.get("lost_to_kind_tokens")}
    return rows, pages, controls, facts


def logit_summary(rows) -> dict:
    """What ``logits_agree`` counts, for the ``phase: serve`` line."""
    groups = {}
    for name, _, err, _ in rows:
        groups.setdefault(name.split(".")[0], []).append(err)
    clean = [r[2] for r in rows if r[2] <= LOGIT_ATOL]
    return {"positions": len(rows), "clean": len(clean),
            "clean_by_group": {g: [sum(e <= LOGIT_ATOL for e in v), len(v)]
                               for g, v in groups.items()},
            "median_by_group": {g: float(np.median(v))
                                for g, v in groups.items()},
            "clean_error_max": max(clean, default=None),
            "error_max": max((r[2] for r in rows), default=None)}


def logits_agree(rows) -> bool:
    """The rule of the header over rows ``(group.what, ..., error,
    margin)``."""
    c = logit_summary(rows)
    return bool(rows) and c["clean"] >= CLEAN_MIN * c["positions"] \
        and c["error_max"] <= FLIP_ATOL


def page_summary(pages) -> dict:
    """What ``pages_agree`` counts: the errors by conv layer, over the
    streams checked."""
    layers = {}
    for name, err in pages:
        layers.setdefault(int(name.rsplit(".", 1)[1]), []).append(err)
    return {"streams": len(layers.get(0, [])),
            "layer0_max": max(layers.get(0, []), default=None),
            "median_by_layer": [float(np.median(layers[l]))
                                for l in sorted(layers)],
            "max_by_layer": [max(layers[l]) for l in sorted(layers)]}


def pages_agree(pages, medians: bool = True) -> bool:
    """The rule of the header over rows ``(stream.layer, error)``;
    ``medians`` off: the toy's routing flips at most positions."""
    c = page_summary(pages)
    return bool(pages) and c["layer0_max"] <= PAGE_RTOL \
        and (not medians
             or max(c["median_by_layer"]) <= PAGE_MEDIAN_RTOL) \
        and max(c["max_by_layer"]) <= PAGE_FLIP_RTOL


def check_served_tokens(reqs, vocab: int, ref_served, width: int):
    """The window's own outputs: FINISHED requests, served inside the full
    batch, the latest-started first, whose prompt + reply fit ``width``
    (``SERVED_WIDTH``: the sessions with the shorter histories: the
    reference's cost grows with the row), until ``SERVED_TOKENS`` tokens
    are checked; each against the reference's teacher-forced forward over
    prompt + emitted tokens.  Returns [(rid, prompt tokens, tokens checked,
    largest gap between the reference's largest logit and the emitted
    token's)]."""
    done = sorted((r for r in reqs if r.t_first is not None
                   and len(r.out_tokens) >= r.max_new_tokens
                   and len(r.prompt) + len(r.out_tokens) <= width),
                  key=lambda r: -r.t_first)
    out = []
    for r in done:
        if sum(n for _, _, n, _ in out) >= SERVED_TOKENS:
            break
        plen, n = len(r.prompt), len(r.out_tokens)
        toks = np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
        lg = ref_served(toks, list(range(plen - 1, plen + n - 1)))[0]
        lg = lg[:, :vocab]
        picked = lg[np.arange(n), np.asarray(r.out_tokens)]
        out.append((r.rid, plen, n, float((lg.max(axis=-1) - picked).max())))
    return out


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

    hist = sessions_traffic.histories(tr, ctx.seed, vocab)
    engine.serve(serve_runner._requests([
        {"rid": -100 - i, "prompt": h, "max_new_tokens": 1, "arrival_s": 0.0}
        for i, h in enumerate(hist)]))
    # (a message's first token stands in for the message: the boundary a
    # session's next turn would resume at, in both kinds)
    histories_cached = [
        engine.prefix_match_tokens(np.concatenate([h, [0]]))
        == len(h) // bs * bs for h in hist]
    ctx.mark("histories")

    rows, pages, controls, facts = check_against_reference(
        engine, sizes, hist, vocab, ctx.seed,
        int(tr["message_len"]["median"]))
    ctx.mark("reference")
    engine.reset_serving_stats()
    items = sessions_traffic.requests(tr, ctx.seed, ctx.seconds, vocab, hist)
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], sessions=len(hist),
            history_tokens=int(sum(len(h) for h in hist)),
            history_blocks=int(sum(-(-len(h) // bs) for h in hist)),
            turns_max=max(r["turn"] for r in items) + 1,
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
    width = min(SERVED_WIDTH, engine.max_len)
    ref_served = _reference(engine, sizes, width,
                            int(tr["output_len"]["max"]))
    served = check_served_tokens(reqs, vocab, ref_served, width)
    wrong = sum(gap > TOKEN_GAP for *_, gap in served)
    served_enough = bool(served) and (
        ctx.rehearsal or sum(n for _, _, n, _ in served) >= SERVED_TOKENS)
    # (the toy's margins are another distribution: 8 experts, top-2)
    agree = logits_agree(rows)
    pages_ok = pages_agree(pages, medians=not ctx.rehearsal)
    # The controls have to fail the comparisons the system has to pass, on
    # the same positions.
    controls_fail = {
        "state_zeroed.logits": not logits_agree(controls["state_zeroed"][0]),
        "state_zeroed.pages": not pages_agree(controls["state_zeroed"][1]),
        "e4m3.logits": not logits_agree(controls["e4m3"][0]),
        "e4m3.pages": not pages_agree(controls["e4m3"][1])}
    resumed = all(
        facts[k + "_resumed_at"] == b and set(
            (facts[k + "_cached_by_class"] or {}).values()) == {b}
        for k, b in (("near", facts["boundary"]),
                     ("turn", facts["boundary"])))
    correct = s["failed"] == 0 and wrong == 0 and served_enough \
        and agree and pages_ok and all(controls_fail.values()) and resumed \
        and all(histories_cached) and compiles_window == 0 \
        and s["output_tokens"] > 0
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "model_counters", "cache_classes", "state")}
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
          "histories_cached": int(sum(histories_cached))}
    # The window's admissions across kinds, from the program's counters:
    # the aggregator's sums since ``reset_serving_stats`` and the
    # allocator's running totals less their values at the window's start.
    state = report.get("state") or {}
    prefix = report.get("prefix") or {}
    admitted = sum(r.t_first is not None for r in reqs)
    window = {
        "admissions": admitted,
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
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, logits_agree=agree,
            logit_summary=logit_summary(rows), page_checks=pages,
            pages_agree=pages_ok, page_summary=page_summary(pages),
            controls={name: {"logits": logit_summary(lg),
                             "pages": page_summary(pg) if pg else None}
                      for name, (lg, pg) in controls.items()},
            controls_fail=controls_fail, facts=facts, window=window,
            limits={"clean": LOGIT_ATOL, "clean_min": CLEAN_MIN,
                    "flipped": FLIP_ATOL, "page_rtol": PAGE_RTOL,
                    "page_median_rtol": PAGE_MEDIAN_RTOL,
                    "page_flip_rtol": PAGE_FLIP_RTOL,
                    "token_gap": TOKEN_GAP, "served_tokens": SERVED_TOKENS},
            served_tokens_checked=served, paged_kernel=engine.paged_kernel,
            max_slots=engine.max_slots, prefill_chunk=engine.prefill_chunk,
            kv=kv, offered_tokens_per_s=sum(r.max_new_tokens for r in reqs)
            / ctx.seconds, snapshot=snapshot, **s)

    record = {
        "kind": "serve", "correct": correct, "attempted": s["attempted"],
        "failed": s["failed"] + wrong,
        "end_to_end": {"serve_tokens_per_s": s["tokens_per_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "summary": s, "snapshot": snapshot, "kv": kv, "sessions": window,
        "afmoe": dict({k: ctx.config[k] for k in COST_KEYS},
                      head_dim=cfg.head_dim),
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

"""kind: mixed_docqa -- short chat turns and questions over long cached
documents in one queue through ``InferenceEngine.serve``, for a
configuration of the ``afmoe`` family: sliding-window and full-attention
layers in two classes of one cache manager, every expert held.

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile the prefill chunk and the decode
step; EVERY DOCUMENT SERVED ONCE (1 new token) through ``engine.serve`` so
that it sits in the prefix cache -- whole in the full class, its last
``sliding_window`` tokens in the window class; the float32 reference
comparison and its controls; ``reset_serving_stats()``.  Window: arrivals
over ``[0, --seconds)`` at the traffic file's fixed rate, above what the
system sustains, cut by the scheduler at the window's end; half the
requests a cached document + an unshared question, half an unshared prompt.
After the window: emitted tokens of finished requests served inside the
full batch against the reference, and every document still in the prefix
cache.

``correct`` (decided on the chip at the published widths, from what the
timed path produced; logits, not tokens), every part of it:
1. logits through the two-class cache against the reference's full forward
   (``lib/afmoe_reference.py``) at FOUR GROUPS of ``GROUP`` positions: (a)
   ``short``: the prefill and the first ``SHORT_STEPS`` decode iterations of
   ``GROUP / (1 + SHORT_STEPS)`` unshared prompts of ``SHORT_LEN`` tokens
   (shorter than the window); (b) ``slid``: one unshared prompt of
   ``SLID_LEN`` tokens (the window has slid: blocks have been returned,
   which is checked): its prefill and its first ``GROUP - 1`` iterations;
   (c) a question over the SHORTEST cached document through the prefix-hit
   path (>= 8k positions; it must have RESUMED at the document's last full
   block in BOTH classes), whose stream then decodes ``LONG_DECODE`` tokens:
   ``doc0_first`` its prefill and first ``GROUP - 1`` iterations,
   ``doc0_last`` its last ``GROUP``.  The rule (``logits_agree``; why
   below): every position whose routing the reference finds DECIDED (margin
   >= ``MARGIN_DECIDED``) within ``LOGIT_ATOL``; of the positions it finds
   LIKELY decided (margin >= ``MARGIN_LIKELY``; there must be ``LIKELY_MIN``
   of them) at least ``LIKELY_CLEAN`` within ``LOGIT_ATOL``; of every
   group's positions at least ``GROUP_CLEAN``; none over ``FLIP_ATOL``;
2. the comparison can fail, shown every run on (c)'s positions: the
   reference with the window OFF, with rotary on the FULL layer too, and
   with 8-bit (e4m3) operands must each come out as NOT agreeing;
3. every emitted token of FINISHED requests served inside the full batch,
   the latest-started first -- one long request over the shortest document
   and short ones until ``SERVED_TOKENS`` tokens are checked -- within
   ``TOKEN_GAP`` of the reference's largest logit in its teacher-forced
   forward; and the long request's tokens against the reference with the
   window OFF must NOT be (that check can fail too);
4. no request over its length, zero compiles in the window, every document
   still matched whole (full class) and by its tail (window class), some
   output.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import afmoe as afmoe_model   # fails at once on a
#                     program that has no such family: nothing has run yet
from perfbench.lib import afmoe_reference as reference
from perfbench.lib import mixed_traffic, traffic as traffic_lib, xplane
from perfbench.runners import _common, serve as serve_runner

# Served logits (bf16 weights and activations, fp32 accumulation, softmax,
# norms and routing, the paged bf16 K/V pools) against the float32 reference
# on the same bf16 weights upcast.  Logits of the randomly initialised model
# have a standard deviation of about 0.9 (unit-RMS final norm x a 2048-wide
# head at std 0.02).  Two kinds of difference (my chip runs, PR 38: thirteen
# runs x 64 positions before the review, ten x 256 since; 3,392 positions;
# PERF.md section 2):
# - rounding: 0.032-0.060 at every position where no routing decision
#   flipped.  LOGIT_ATOL 0.15: 2.5 times that, under half the least flip.
# - a flipped routing decision, 0.32-1.57 (nothing was read between 0.060
#   and 0.32): top-8 of 128 is discrete, and here near-ties are the RULE.  The
#   reference reports per position the least gap over the four expert layers
#   between its 8th and 9th candidate (``margin``, in units of c = s + b); the
#   served c carries noise of about 0.002 (bf16 residual stream).  Read: a
#   fifth of the positions flipped (20.7%; 42-56 clean of a group's 64); by
#   margin 52% of those under 0.0005, 22% at 0.001-0.002, 3.4% at
#   0.003-0.004, 2.2% at 0.004-0.005, 1 of 302 at 0.005-0.007 (the largest:
#   0.0060), 0 of 355 from 0.007 up.  15 positions of 3,392 had a margin >=
#   0.016 (docqa.py's limit for 256 experts in 8 groups), 1.9% >= 0.012, 26%
#   >= 0.004.  So docqa.py's rule does not carry over (its exact clause would
#   apply once in four runs and its count of flips would refuse a run in
#   fifteen), and a limit on clean positions in a group of 16 cannot be tight
#   (fewer than 8 clean: once in 700 groups at a fifth).  This rule instead,
#   on 256 positions in four groups of 64 (each a path: short prompts, the
#   slid window, the prefix hit's first and last iterations):
#   DECIDED (margin >= MARGIN_DECIDED 0.012: twice the largest margin a flip
#   was read at; the share of flips falls by about 0.4 every 0.001, which
#   puts one there at 1 in 10^5 positions; read: 2-11 of them a run, 65 in
#   all, 0.038-0.047 every one) is held to LOGIT_ATOL, each;
#   LIKELY decided (margin >= MARGIN_LIKELY 0.004: read 57-80 of 256 a run,
#   6 flips in 887) must be clean at LIKELY_CLEAN 0.85 -- eleven flips of 67
#   at a rate of 1-2 in 100 happen once in 10^8 runs or rarer -- and number
#   at least LIKELY_MIN 24 (5 standard deviations under the least read), so
#   the clause always has something to hold: a fault that spoils a sixth of
#   what the reference finds decided fails;
#   every GROUP holds GROUP_CLEAN 0.5 clean positions (33 flips of 64 at a
#   rate of 0.21: once in 10^7 groups; at 0.27, the most a run read, once in
#   40,000), so a fault confined to one path that spoils a third of it fails;
#   a flip may not exceed FLIP_ATOL 3.0 (twice the largest read, 1.57).
#   A clean position cannot come from a faulty path, a fault being
#   systematic: the reference with rotary on the full layer reads 0.47-1.54
#   at EVERY position, the window off 4.3-6.2, e4m3 operands 2.45-3.71
#   (each fails every clause, every run: ``controls``, ``controls_fail``).
# - tokens: one the served path emits after a flip lies within twice a flip
#   of the reference's largest logit; against the reference with the window
#   OFF (what a path that read past the window would have emitted from) the
#   served tokens' largest gap is read every run as ``served_tokens_control``
#   and must lie over the limit.  Read: 0.08-0.85 over 320-390 tokens of
#   three or four finished requests a run (0.0005-0.89 over 8-38 tokens of
#   one before the review); the control 4.69-5.71.  TOKEN_GAP 3.0: 3.4 times
#   the one, 1.6 under the other.
LOGIT_ATOL = 0.15
FLIP_ATOL = 3.0
MARGIN_DECIDED = 0.012
MARGIN_LIKELY = 0.004
LIKELY_CLEAN = 0.85
LIKELY_MIN = 24
GROUP_CLEAN = 0.5
TOKEN_GAP = 3.0
SERVED_TOKENS = 300
GROUP = 64
SHORT_STEPS = 3
SHORT_LEN = 600
SLID_LEN = 3000
LONG_DECODE = 256
SPANS = serve_runner.SPANS
KEYS = ("hidden_size", "moe_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts",
        "num_experts_per_tok", "num_hidden_layers", "num_dense_layers",
        "sliding_window")


def model_config(sizes: dict):
    """The program's AfmoeConfig from the configuration file: the published
    keys as published (of ``layer_types`` the first ``num_hidden_layers``)."""
    return afmoe_model.AfmoeConfig.from_hf(
        sizes, initializer_range=float(sizes["assumed"]["initializer_range"]))


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: afmoe_model.afmoe_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def _reference(engine, sizes, width: int, n_out: int, q_block: int, cast=None):
    """One compiled reference for token rows padded to ``width`` (causal:
    padding after the real tokens changes nothing before it) and ``n_out``
    output positions; the controls' switches are traced flags."""
    fn = jax.jit(lambda p, t, out, flags: reference.forward(
        p, t, sizes, out_positions=out, q_block=q_block, cast=cast,
        window=flags[0], rotary_all=flags[1], gate=flags[2]))

    def run(tokens, out_positions, window=True, rotary_all=False, gate=True):
        row = np.zeros(width, np.int32)
        row[:len(tokens)] = tokens
        out = np.zeros(n_out, np.int32)
        out[:len(out_positions)] = out_positions
        lg, margin = fn(engine._params, jnp.asarray(row), jnp.asarray(out),
                        jnp.asarray([window, rotary_all, gate]))
        n = len(out_positions)
        return np.asarray(lg)[:n], np.asarray(margin)[:n]
    return run


def _class_state(engine) -> dict:
    return {name: dict(st) for name, st in
            engine.allocator.class_stats().items()}


def _through_the_cache(engine, prompt, steps=(1,)):
    """(tokens emitted, logits of the prefill and of the decode iterations
    ``steps`` (1 = the first), admission info) of ``prompt`` served alone
    through the engine's own admission, prefill and decode, ``max(steps)``
    iterations."""
    last = max(steps)
    slot = engine.select_slot(prompt, 1 + last)
    tok, pre = engine.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=1 + last)
    info = dict(engine.last_admit_info(slot))
    engine.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre, np.float32)]
    for i in range(1, last + 1):
        sampled, dec = engine.decode_once(return_logits=i in steps)
        toks.append(int(sampled[slot]))
        if i in steps:
            got.append(np.asarray(dec[slot], np.float32))
    info["classes"] = _class_state(engine)
    engine.release_slot(slot)
    return toks, np.stack(got), info


def _rows(group, names, info, got, want, margin, vocab):
    return [(f"{group}.{name}", info.get("cached_tokens", 0),
             float(np.abs(got[j, :vocab] - want[j, :vocab]).max()),
             float(margin[j])) for j, name in enumerate(names)]


def check_against_reference(engine, sizes, docs, vocab: int, seed: int,
                            ref_long, ref_long_8bit):
    """([(group.what, cached tokens, |logit error| max, margin)] per checked
    position, {control: the same at (c)'s positions}, facts about (b) and
    (c))."""
    rng = np.random.default_rng([seed, 3])
    short = min(SHORT_LEN, engine.max_len // 4)
    steps = list(range(1, SHORT_STEPS + 1))
    ref_short = _reference(engine, sizes, short + SHORT_STEPS,
                           1 + SHORT_STEPS, 128)
    rows = []
    for i in range(GROUP // (1 + SHORT_STEPS)):                # (a)
        prompt = rng.integers(0, vocab, size=short, dtype=np.int32)
        toks, got, info = _through_the_cache(engine, prompt, steps)
        want, margin = ref_short(np.concatenate([prompt, toks[:-1]]),
                                 [short - 1 + j for j in [0] + steps])
        rows += _rows("short", [f"{i}.prefill"] + [f"{i}.{j}" for j in steps],
                      info, got, want, margin, vocab)
    slid = min(SLID_LEN, engine.max_len // 2)                  # (b)
    prompt = rng.integers(0, vocab, size=slid, dtype=np.int32)
    before = _class_state(engine)
    steps = list(range(1, GROUP))
    toks, got, info = _through_the_cache(engine, prompt, steps)
    returned = {name: st["returned"] - before[name]["returned"]
                for name, st in info["classes"].items()}
    want, margin = ref_long(np.concatenate([prompt, toks[:-1]]),
                            [slid - 1 + i for i in [0] + steps])
    rows += _rows("slid", ["prefill"] + steps, info, got, want, margin,
                  vocab)
    q = rng.integers(0, vocab, size=96, dtype=np.int32)        # (c)
    prompt = np.concatenate([docs[0], q])
    more = max(min(LONG_DECODE, engine.max_len // 8), 2 * GROUP - 1)
    first, last = steps, list(range(more - GROUP + 1, more + 1))
    toks, got, info = _through_the_cache(engine, prompt, first + last)
    seq = np.concatenate([prompt, toks[:-1]])
    at = [len(prompt) - 1 + i for i in [0] + first + last]

    def doc0_rows(logits, margin):
        return _rows("doc0_first", ["prefill"] + first, info, logits[:GROUP],
                     want[:GROUP], margin[:GROUP], vocab) \
            + _rows("doc0_last", last, info, logits[GROUP:], want[GROUP:],
                    margin[GROUP:], vocab)
    want, margin = ref_long(seq, at)
    rows += doc0_rows(got, margin)
    controls = {}
    for name, (fn, kw) in {
            "window_off": (ref_long, {"window": False}),
            "rotary_on_full": (ref_long, {"rotary_all": True}),
            "e4m3": (ref_long_8bit, {})}.items():
        # What the control reads against the TRUE reference: the error the
        # served path would show if it computed that instead.
        controls[name] = doc0_rows(fn(seq, at, **kw)[0], margin)
    bs = engine.block_size
    facts = {"window_blocks_returned_by_slid_prompt": returned,
             "doc0_resumed_at": info.get("cached_tokens", 0),
             "doc0_cached_by_class": info.get("cached_by_class"),
             "doc0_full_blocks": len(docs[0]) // bs * bs}
    return rows, controls, facts


def logit_summary(rows) -> dict:
    """What ``logits_agree`` counts, for the ``phase: serve`` line."""
    groups = {}
    for name, _, err, _ in rows:
        groups.setdefault(name.split(".")[0], []).append(err <= LOGIT_ATOL)
    likely = [r[2] <= LOGIT_ATOL for r in rows if r[3] >= MARGIN_LIKELY]
    decided = [r[2] for r in rows if r[3] >= MARGIN_DECIDED]
    clean = [r[2] for r in rows if r[2] <= LOGIT_ATOL]
    return {"positions": len(rows),
            "clean_by_group": {g: [sum(v), len(v)] for g, v in groups.items()},
            "likely": len(likely), "likely_clean": sum(likely),
            "decided": len(decided),
            "decided_error_max": max(decided, default=None),
            "clean_error_max": max(clean, default=None),
            "error_max": max(r[2] for r in rows)}


def logits_agree(rows, likely_min: int = 0) -> bool:
    """The rule of the header, over rows ``(group.what, ..., error,
    margin)``; ``likely_min``: the LIKELY decided positions there must be."""
    c = logit_summary(rows)
    return all(n >= GROUP_CLEAN * of
               for n, of in c["clean_by_group"].values()) \
        and c["likely"] >= likely_min \
        and c["likely_clean"] >= LIKELY_CLEAN * c["likely"] \
        and (c["decided_error_max"] or 0.0) <= LOGIT_ATOL \
        and c["error_max"] <= FLIP_ATOL


def check_served_tokens(reqs, doc_of, vocab: int, ref_long):
    """The window's own outputs: FINISHED requests, served inside the full
    batch, the latest-started first: one LONG request over the shortest
    document (through blocks the set-up wrote in both classes), then SHORT
    ones until ``SERVED_TOKENS`` tokens are checked; each against the
    reference's teacher-forced forward over prompt + emitted tokens.
    Returns ([(rid, kind, tokens checked, largest gap between the
    reference's largest logit and the emitted token's)], the long request's
    gap against the reference with the window OFF or None)."""
    done = sorted((r for r in reqs if r.t_first is not None
                   and len(r.out_tokens) >= r.max_new_tokens),
                  key=lambda r: -r.t_first)
    long = [r for r in done if doc_of[r.rid] == 0][:1]
    short = [r for r in done if doc_of[r.rid] < 0]

    def gap(r, **kw):
        plen, n = len(r.prompt), len(r.out_tokens)
        toks = np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
        lg, _ = ref_long(toks, list(range(plen - 1, plen + n - 1)), **kw)
        lg = lg[:, :vocab]
        picked = lg[np.arange(n), np.asarray(r.out_tokens)]
        return float((lg.max(axis=-1) - picked).max())
    out = [(r.rid, "long", len(r.out_tokens), gap(r)) for r in long]
    for r in short:
        if sum(n for _, _, n, _ in out) >= SERVED_TOKENS and len(out) > 1:
            break
        out.append((r.rid, "short", len(r.out_tokens), gap(r)))
    return out, gap(long[0], window=False) if long else None


def measure(engine, items, seconds: float):
    """``serve_runner.measure`` with the live blocks sampled by class."""
    reqs = serve_runner._requests(items)
    live, by_class, done = [], [], threading.Event()

    def sample():
        while not done.wait(1.0):
            live.append(engine.allocator.blocks_in_use())
            lens = engine.lengths[engine.active].astype(np.int64)
            by_class.append({name: {
                "live": st["live"],
                # key rows a LAYER of the class may read this iteration
                "key_rows": int((lens if st["reach"] is None else
                                 np.minimum(lens, st["reach"])).sum())}
                for name, st in engine.allocator.class_stats().items()})
    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()
    t = time.perf_counter()
    report = engine.serve(reqs, temperature=0.0, max_wall_s=seconds)
    wall = time.perf_counter() - t
    done.set()
    sampler.join()
    return reqs, report, wall, live, by_class


def run(ctx):
    tr = ctx.traffic
    sizes = dict(ctx.config)
    vocab = int(ctx.config["vocab_size"])
    cfg, engine = build_engine(ctx)
    ctx.mark("weights_and_engine")
    serve_runner.warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)

    docs = mixed_traffic.documents(tr, ctx.seed, vocab)
    engine.serve(serve_runner._requests([
        {"rid": -100 - i, "prompt": d, "max_new_tokens": 1, "arrival_s": 0.0}
        for i, d in enumerate(docs)]))
    doc_blocks = [len(d) // engine.block_size for d in docs]
    ctx.mark("documents")

    longest = len(docs[0]) + tr["question_len"]["max"] \
        + tr["output_len"]["max"]
    width = -(-longest // 512) * 512
    n_out = max(tr["output_len"]["max"], 2 * GROUP)
    ref_long = _reference(engine, sizes, width, n_out, 128)
    ref_long_8bit = _reference(engine, sizes, width, 2 * GROUP, 128,
                               cast=jnp.float8_e4m3fn)
    rows, controls, facts = check_against_reference(
        engine, sizes, docs, vocab, ctx.seed, ref_long, ref_long_8bit)
    ctx.mark("reference")
    engine.reset_serving_stats()
    items = mixed_traffic.requests(tr, ctx.seed, ctx.seconds, vocab, docs)
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], documents=len(docs),
            document_tokens=int(sum(len(d) for d in docs)),
            document_blocks=int(sum(-(-len(d) // engine.block_size)
                                    for d in docs)))

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
    setup_s = time.perf_counter() - ctx.t0
    if tracer:
        tracer.start()
    reqs, report, wall, live, live_by_class = measure(engine, items,
                                                      ctx.seconds)
    if tracer:
        tracer.join()
    compiles_window = int(ctx.compile_events.get("n", 0))

    s = serve_runner.summarize(reqs, wall)
    # Every document still in the prefix cache: matched whole by the full
    # class and by its tail in the window class (the hit every class can
    # serve is the whole document's blocks).
    bs = engine.block_size
    docs_whole = [engine.prefix_match_tokens(np.concatenate([d, d[:1]]))
                  // bs == n for d, n in zip(docs, doc_blocks)]
    served, served_control = check_served_tokens(
        reqs, {r["rid"]: r["shared"] for r in items}, vocab, ref_long)
    wrong = sum(gap > TOKEN_GAP for *_, gap in served)
    served_enough = {kind for _, kind, _, _ in served} == {"long", "short"} \
        and (ctx.rehearsal
             or sum(n for _, _, n, _ in served) >= SERVED_TOKENS)
    # The toy's margins are another distribution (8 experts, top-2).
    agree = logits_agree(rows, 0 if ctx.rehearsal else LIKELY_MIN)
    controls_fail = {name: not logits_agree(c) for name, c in controls.items()}
    controls_fail["served_tokens_window_off"] = \
        served_control is not None and served_control > TOKEN_GAP
    resumed = facts["doc0_resumed_at"] == facts["doc0_full_blocks"] \
        and all(v > 0 for v in (facts["doc0_cached_by_class"] or {}).values())
    slid = all(v > 0 for name, v in
               facts["window_blocks_returned_by_slid_prompt"].items()
               if engine.allocator.class_stats()[name]["reach"] is not None)
    correct = s["failed"] == 0 and wrong == 0 and served_enough \
        and agree and all(controls_fail.values()) and resumed \
        and slid and compiles_window == 0 and all(docs_whole) \
        and s["output_tokens"] > 0
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "model_counters", "cache_classes")}
    half = live[len(live) // 2:]
    classes1 = _class_state(engine)
    by_class = {}
    for name, st in classes1.items():
        seen = [row[name]["live"] for row in live_by_class if name in row]
        later = seen[len(seen) // 2:]
        rows_later = [row[name]["key_rows"] for row in live_by_class
                      if name in row][len(seen) // 2:]
        by_class[name] = {
            "num_blocks": st["blocks"], "reach": st["reach"],
            "live_blocks_mean": float(np.mean(later)) if later else None,
            "live_blocks_max": max(seen, default=None),
            "key_rows_a_layer_mean":
                float(np.mean(rows_later)) if rows_later else None,
            "returned_in_window": st["returned"] - classes0[name]["returned"],
            "reclaimed_in_window":
                st["reclaimed"] - classes0[name]["reclaimed"]}
    kv = {"num_blocks": int(sum(st["blocks"] for st in classes1.values())),
          "block_bytes": {sp.name: sp.block_nbytes()
                          for sp in engine.cache_specs},
          "live_blocks_mean": float(np.mean(half)) if half else None,
          "live_blocks_max": max(live, default=None),
          "live_blocks_by_second": live,
          "classes": by_class,
          "documents_whole": int(sum(docs_whole))}
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, logits_agree=agree,
            logit_summary=logit_summary(rows),
            controls={name: logit_summary(c) for name, c in controls.items()},
            controls_fail=controls_fail, facts=facts,
            limits={"clean": LOGIT_ATOL, "flipped": FLIP_ATOL,
                    "margin_decided": MARGIN_DECIDED,
                    "margin_likely": MARGIN_LIKELY,
                    "likely_clean": LIKELY_CLEAN, "likely_min": LIKELY_MIN,
                    "group_clean": GROUP_CLEAN, "token_gap": TOKEN_GAP,
                    "served_tokens": SERVED_TOKENS},
            served_tokens_checked=served,
            served_tokens_control=served_control,
            paged_kernel=engine.paged_kernel,
            max_slots=engine.max_slots, prefill_chunk=engine.prefill_chunk,
            kv=kv, offered_tokens_per_s=sum(r.max_new_tokens for r in reqs)
            / ctx.seconds, snapshot=snapshot, **s)

    record = {
        "kind": "serve", "correct": correct, "attempted": s["attempted"],
        "failed": s["failed"] + wrong,
        "end_to_end": {"serve_tokens_per_s": s["tokens_per_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "summary": s, "snapshot": snapshot, "kv": kv,
        "afmoe": {k: ctx.config[k] for k in KEYS},
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

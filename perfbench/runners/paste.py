"""kind: paste -- long UNSHARED prompts (a pasted file, log, thread or
article nobody else sends) answered with a few hundred tokens, from a
standing backlog, through ``InferenceEngine.serve``, for a configuration of
the ``smallthinker`` family: every layer an expert layer routed from the
block's input ahead of its attention, a position-free full layer and three
rotary window layers a period in two classes of one cache manager.

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile the prefill chunk and the decode
step; EVERY SHARED SYSTEM PROMPT SERVED ONCE (1 new token) through
``engine.serve`` so that it sits in the prefix cache; the SERVED side of the
reference comparison (four groups of prompts through the timed path's own
programs, their logits kept on the host); ``reset_serving_stats()``.
Window: ``backlog`` requests due at 0 and an open loop over ``[0,
--seconds)`` at the traffic file's fixed rate, above what the system
sustains, cut by the scheduler at the window's end (``lib/reason_traffic
.py``): every admission is 2-30 chunk programs of 512 rows, half of them
across the window's edge, beside ``max_slots`` live streams.  After the
window: the pools are dropped (they have done their work) and the float32
reference runs where they lay — its forward over 16k positions does not fit
beside 14.4 GB of weights and pools — over the four groups, the controls and
the emitted tokens of two requests served inside the full batch.

``correct`` (decided on the chip at the published widths, from what the
timed path produced; logits, not tokens), every part of it:
1. logits through the two-class cache against the reference's full forward
   (``lib/smallthinker_reference.py``) at FOUR GROUPS of ``GROUP``
   positions: ``short``: the prefill and the first ``SHORT_STEPS`` decode
   iterations of ``GROUP / (1 + SHORT_STEPS)`` unshared prompts of
   ``SHORT_LEN`` tokens; ``slid``: one unshared prompt of ``SLID_LEN``
   tokens, longer than the window (the ring must have returned blocks
   DURING ITS PREFILL: counted), its last chunk's logits and its first
   ``GROUP - 1`` iterations; ``long``: one of ``LONG_LEN`` tokens, near the
   position limit, the same; ``shared``: a prompt behind a cached system
   prompt (it must have RESUMED at the system prompt's end in BOTH classes),
   the same.  The rule (``logits_agree``; why below): every position whose
   routing the reference finds DECIDED (margin >= ``MARGIN_DECIDED``) within
   ``LOGIT_ATOL``; the run's MEDIAN error within ``MEDIAN_ATOL``; of the
   positions it finds LIKELY decided (margin >= ``MARGIN_LIKELY``; there
   must be ``LIKELY_MIN`` of them) at least ``LIKELY_CLEAN`` within
   ``LOGIT_ATOL``; of every group's positions at least ``GROUP_CLEAN``; none
   over ``FLIP_ATOL``;
2. the comparison can fail, shown every run on ``slid``'s positions: the
   reference with 8-bit (e4m3) operands, with the window OFF, with rotary on
   the FULL layers too, with the router reading the POST-attention norm (the
   usual placement), with SiLU for ReLU, and with a softmax over all the
   logits before the choice and no renormalising must each come out as NOT
   agreeing;
3. every emitted token of two FINISHED requests served inside the full
   batch, the latest-started that ends past ``sliding_window_size``
   positions and the latest-started behind a system prompt, within
   ``TOKEN_GAP`` of the reference's largest logit in its teacher-forced
   forward;
4. no request over its length, zero compiles in the window, every system
   prompt resumable at its end when the window opens, blocks returned
   during prefill inside the window, some output.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import smallthinker as model   # fails at once on
#           a program that has no such family: nothing has touched a device
from perfbench.lib import reason_traffic, smallthinker_costs
from perfbench.lib import smallthinker_reference as reference
from perfbench.lib import traffic as traffic_lib, xplane
from perfbench.runners import _common, serve as serve_runner
from perfbench.runners.mixed_docqa import measure

# Served logits (bf16 weights and activations, fp32 accumulation, softmax,
# norms and routing, the paged bf16 K/V pools) against the float32 reference
# on the same bf16 weights upcast.  Logits of the seeded model have a
# standard deviation of about 1 (unit-RMS final norm x a head at 2560^-1/2).
# Two kinds of difference, as in ``mixed_docqa.py`` (my chip runs, PR 54 c1,
# c3 and c4: eight runs x 256 positions = 2,048, on which the numbers below
# stand; c5's six more runs pass every limit with the same room (a group
# 40-57 clean); PERF.md section 6):
# - rounding: 0.037-0.082 at every position where no routing decision
#   flipped (a run's median 0.048-0.052).  LOGIT_ATOL 0.12: 1.5 times that,
#   three quarters of the least flip.
# - a flipped routing decision, 0.16-1.86 (nothing was read between 0.082
#   and 0.163): top-6 of 64 by LOGIT is discrete, and here near-ties are the
#   RULE.  The reference reports per position the least gap over the 8 layers
#   between its 6th and 7th logit (``margin``: its median over positions is
#   0.007-0.009, the least of eight gaps of ~0.1), and the served router
#   logits carry the noise of a bf16 residual stream.  A flip swaps the least
#   of six softmax weights' expert for its neighbour's.  Read: a quarter of
#   the positions flipped (24%; 39-53 clean of a group's 64); by margin 49%
#   of those under 0.005, 24% at 0.005-0.01, 10% at 0.01-0.02, 1.7% (3 of
#   176) at 0.02-0.03, 2 of 98 at 0.03-0.05 (the largest: 0.0373), 0 of 26
#   from 0.05 up.  This rule, on 256 positions in four groups of 64 (each a
#   path: a prefix hit in both classes, short prompts, the window sliding
#   during prefill, a prompt near the position limit):
#   DECIDED (margin >= MARGIN_DECIDED 0.075: twice the largest margin a flip
#   was read at; 0-2 of them a run) is held to LOGIT_ATOL, each;
#   the run's MEDIAN error to MEDIAN_ATOL 0.10 (twice the 0.048-0.052 read;
#   a sixth of the least control's median, 0.60): a systematic fault moves
#   every position, flips move a minority;
#   LIKELY decided (margin >= MARGIN_LIKELY 0.02: read 34-63 of 256 a run, 6
#   flips in 333) must be clean at LIKELY_CLEAN 0.85 -- six flips of 34 at a
#   rate of 2 in 100 happen once in 10^7 runs -- and number at least
#   LIKELY_MIN 12 (5.8 standard deviations under the mean read), so the
#   clause always has something to hold;
#   every GROUP holds GROUP_CLEAN 0.4 clean positions (read: 39-53 of 64,
#   mean 48.4; a group is one stream's consecutive positions, whose flips
#   are not independent -- a flipped position's K/V rows are read by the
#   later ones -- so the least read lies 2.8 standard deviations out and the
#   limit stands at 26, not at 32), so a fault confined to one path fails:
#   it spoils ALL of a path's positions (every control reads 0 clean of 64);
#   a flip may not exceed FLIP_ATOL 4.0 (2.15 times the largest read, 1.86).
#   Each control reads, at EVERY one of its 64 positions, an error over
#   LOGIT_ATOL: rotary on the full layers 0.60-0.67 (median), the window off
#   0.69-0.74, the router on the post-attention norm 0.87-1.00, SiLU for
#   ReLU 1.53-1.62, e4m3 operands 1.51-1.72, a softmax over all 64 logits
#   2.41-2.47 (each fails the group, the median and the likely clause,
#   every run: ``controls``, ``controls_fail``).
# - tokens: one the served path emits after a flip lies within twice a flip
#   of the reference's largest logit.  Read: 0.10-0.74 over 60-101 tokens of
#   each of fourteen finished requests.  TOKEN_GAP 3.0 (cell 6's): four times
#   the largest read; no control reads it here (the window off moves this
#   model's logits by 0.7, not by the 4-6 it moves cell 6's).
LOGIT_ATOL = 0.12
MEDIAN_ATOL = 0.10
FLIP_ATOL = 4.0
MARGIN_DECIDED = 0.075
MARGIN_LIKELY = 0.02
LIKELY_CLEAN = 0.85
LIKELY_MIN = 12
GROUP_CLEAN = 0.4
TOKEN_GAP = 3.0
GROUP = 64
SHORT_STEPS = 3
SHORT_LEN = 600
SLID_LEN = 6000
LONG_LEN = 15000
SHARED_TAIL = 1000
SPANS = serve_runner.SPANS
CONTROLS = {"e4m3": {},
            "window_off": {"window": False},
            "rotary_on_full": {"rotary_all": True},
            "router_reads_post_norm": {"router_post": True},
            "silu_for_relu": {"silu": True},
            "softmax_over_all": {"softmax_all": True}}


def model_config(sizes: dict):
    """The program's SmallthinkerConfig from the configuration file: the
    published keys as published (of the two layouts the first
    ``num_hidden_layers`` entries), in ``assumed.compute_dtype``."""
    return model.SmallthinkerConfig.from_hf(
        sizes, dtype=jnp.dtype(sizes["assumed"]["compute_dtype"]))


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: model.smallthinker_init(key, cfg))(
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


def serve_system_prompts(engine, system) -> list:
    """Each served once (1 new token); whether a request behind it would
    resume at its end (a message's first token stands in for the message)."""
    engine.serve(serve_runner._requests([
        {"rid": -100 - i, "prompt": p, "max_new_tokens": 1, "arrival_s": 0.0}
        for i, p in enumerate(system)]))
    bs = engine.block_size
    return [engine.prefix_match_tokens(np.concatenate([p, [0]]))
            == len(p) // bs * bs for p in system]


def _reference(params, sizes, width: int, n_out: int, q_block: int,
               cast=None):
    """One compiled reference for token rows padded to ``width`` (causal:
    padding after the real tokens changes nothing before it) and ``n_out``
    output positions; the controls' switches are traced flags."""
    fn = jax.jit(lambda p, t, out, flags: reference.forward(
        p, t, sizes, out_positions=out, q_block=q_block, cast=cast,
        **dict(zip(reference.FLAGS, flags))))

    def run(tokens, out_positions, **flags):
        row = np.zeros(width, np.int32)
        row[:len(tokens)] = tokens
        out = np.zeros(n_out, np.int32)
        out[:len(out_positions)] = out_positions
        on = dict(reference.TRUE_MODEL, **flags)
        lg, margin = fn(params, jnp.asarray(row), jnp.asarray(out),
                        jnp.asarray([on[f] for f in reference.FLAGS]))
        n = len(out_positions)
        return np.asarray(lg)[:n], np.asarray(margin)[:n]
    return run


def _class_state(engine) -> dict:
    return {name: dict(st) for name, st in
            engine.allocator.class_stats().items()}


def _through_the_cache(engine, prompt, steps):
    """(tokens emitted, logits of the prefill and of the decode iterations
    ``steps`` (1 = the first), admission info) of ``prompt`` served alone
    through the engine's own admission, prefill and decode."""
    last = max(steps)
    before = _class_state(engine)
    slot = engine.select_slot(prompt, 1 + last)
    tok, pre = engine.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=1 + last)
    info = dict(engine.last_admit_info(slot))
    info["returned_by_prefill"] = {
        name: st["returned"] - before[name]["returned"]
        for name, st in _class_state(engine).items()}
    engine.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre, np.float32)]
    for i in range(1, last + 1):
        sampled, dec = engine.decode_once(return_logits=i in steps)
        toks.append(int(sampled[slot]))
        if i in steps:
            got.append(np.asarray(dec[slot], np.float32))
    engine.release_slot(slot)
    return toks, np.stack(got), info


def lengths_of(engine) -> dict:
    """The four groups' prompt lengths: the header's, cut to a toy engine's
    positions in the CPU rehearsal."""
    room = engine.max_len - GROUP - 1
    return {"short": min(SHORT_LEN, engine.max_len // 8),
            "slid": min(SLID_LEN, engine.max_len * 3 // 8),
            "long": min(LONG_LEN, room),
            "shared_tail": min(SHARED_TAIL, engine.max_len // 16)}


def serve_the_groups(engine, system, vocab: int, seed: int):
    """The SERVED side of the comparison, through the timed path's own
    programs: [(group, names, token row, output positions, served logits,
    admission info)] and the facts ``correct`` reads."""
    rng = np.random.default_rng([seed, 3])
    n = lengths_of(engine)
    out, facts = [], {}

    def one(group, prompt):
        steps = list(range(1, GROUP))
        toks, got, info = _through_the_cache(engine, prompt, steps)
        out.append((group, ["prefill"] + steps,
                    np.concatenate([prompt, toks[:-1]]),
                    [len(prompt) - 1 + j for j in [0] + steps], got, info))
        facts[group] = {"prompt_tokens": len(prompt),
                        "resumed_at": info.get("cached_tokens", 0),
                        "cached_by_class": info.get("cached_by_class"),
                        "chunks": info.get("chunks"),
                        "returned_by_prefill": info["returned_by_prefill"]}
    # (first: before the other groups' tails can push the system prompt's
    # out of a small window pool)
    one("shared", np.concatenate([system[0], rng.integers(
        0, vocab, size=n["shared_tail"], dtype=np.int32)]))
    steps = list(range(1, SHORT_STEPS + 1))
    for i in range(GROUP // (1 + SHORT_STEPS)):
        prompt = rng.integers(0, vocab, size=n["short"], dtype=np.int32)
        toks, got, info = _through_the_cache(engine, prompt, steps)
        out.append(("short", [f"{i}.prefill"] + [f"{i}.{j}" for j in steps],
                    np.concatenate([prompt, toks[:-1]]),
                    [len(prompt) - 1 + j for j in [0] + steps], got, info))
    one("slid", rng.integers(0, vocab, size=n["slid"], dtype=np.int32))
    one("long", rng.integers(0, vocab, size=n["long"], dtype=np.int32))
    facts["system_prompt_blocks"] = len(system[0]) // engine.block_size \
        * engine.block_size
    return out, facts


def _rows(group, names, info, got, want, margin, vocab):
    return [(f"{group}.{name}", info.get("cached_tokens", 0),
             float(np.abs(got[j, :vocab] - want[j, :vocab]).max()),
             float(margin[j])) for j, name in enumerate(names)]


def hold_to_the_reference(served, refs, vocab: int):
    """([(group.what, cached tokens, |logit error| max, margin)] per checked
    position, {control: the same at ``slid``'s positions})."""
    rows, controls = [], {}
    for group, names, seq, at, got, info in served:
        ref = refs["short" if group == "short" else
                   "long" if group == "long" else "mid"]
        want, margin = ref(seq, at)
        rows += _rows(group, names, info, got, want, margin, vocab)
        if group != "slid":
            continue
        for name, flags in CONTROLS.items():
            fn = refs["mid_8bit"] if name == "e4m3" else ref
            # What the control reads against the TRUE reference: the error
            # the served path would show if it computed that instead.
            controls[name] = _rows(group, names, info, fn(seq, at, **flags)[0],
                                   want, margin, vocab)
    return rows, controls


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
            "error_median": float(np.median([r[2] for r in rows])),
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
        and c["error_median"] <= MEDIAN_ATOL \
        and c["error_max"] <= FLIP_ATOL


def pick_served(reqs, shared_of, reach: int):
    """The two requests whose every emitted token is checked, of those
    that FINISHED (served inside the full batch): the latest-started that
    ends past ``reach`` positions, and the latest-started other one behind
    a system prompt (any other one where none finished)."""
    done = sorted((r for r in reqs if r.t_first is not None
                   and len(r.out_tokens) >= r.max_new_tokens),
                  key=lambda r: -r.t_first)
    past = [r for r in done if len(r.prompt) + len(r.out_tokens) > reach][:1]
    rest = [r for r in done if r not in past]
    behind = [r for r in rest if shared_of[r.rid] >= 0][:1] or rest[:1]
    return [("past_window", r) for r in past] \
        + [("behind_system_prompt", r) for r in behind]


def token_gap(ref, r, vocab: int) -> float:
    """The largest gap between the reference's largest logit and the
    emitted token's, over ``r``'s emitted tokens, teacher-forced."""
    plen, n = len(r.prompt), len(r.out_tokens)
    toks = np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
    lg, _ = ref(toks, list(range(plen - 1, plen + n - 1)))
    lg = lg[:, :vocab]
    picked = lg[np.arange(n), np.asarray(r.out_tokens)]
    return float((lg.max(axis=-1) - picked).max())


def run(ctx):
    tr = ctx.traffic
    sizes = dict(ctx.config)
    vocab = int(ctx.config["vocab_size"])
    cfg, engine = build_engine(ctx)
    ctx.mark("weights_and_engine")
    serve_runner.warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)

    items = reason_traffic.requests(tr, ctx.seed, ctx.seconds, vocab)
    system = system_prompts(items, int(tr["shared_prefix"]["tokens"]))
    serve_system_prompts(engine, system)
    ctx.mark("system_prompts")
    served, facts = serve_the_groups(engine, system, vocab, ctx.seed)
    # (the groups' prompts may have pushed a system prompt's tail out of the
    # window class: they go in again, and are checked as the window opens)
    system_cached = serve_system_prompts(engine, system)
    ctx.mark("served_groups")
    engine.reset_serving_stats()
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], backlog=tr["backlog"],
            system_prompts=len(system))

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
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "model_counters", "cache_classes",
        "prefill_window_blocks_returned", "cached_tokens_full",
        "cached_tokens_window")}
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
          "live_blocks_by_second": live, "classes": by_class,
          "system_prompts_cached": int(sum(system_cached))}
    peak_window = _common.memory_peak_bytes(ctx.devices)
    paged_kernel, max_slots = engine.paged_kernel, engine.max_slots
    prefill_chunk, max_len = engine.prefill_chunk, engine.max_len
    reach = int(ctx.config["sliding_window_size"])
    params = engine._params

    # The reference's rows are up to max_seq_len wide and float32: the pools
    # have done their work and make room for them.
    engine.cache.clear()
    n = lengths_of(engine)
    wide = -(-max_len // 128) * 128
    mid = -(-(max(n["slid"], len(system[0]) + n["shared_tail"]) + GROUP)
            // 128) * 128
    refs = {"short": _reference(params, sizes, n["short"] + SHORT_STEPS,
                                1 + SHORT_STEPS, 128),
            "mid": _reference(params, sizes, mid, GROUP, 128),
            "mid_8bit": _reference(params, sizes, mid, GROUP, 128,
                                   cast=jnp.float8_e4m3fn),
            "long": _reference(params, sizes, wide,
                               max(GROUP, int(tr["output_len"]["max"])), 64)}
    rows, controls = hold_to_the_reference(served, refs, vocab)
    checked, wrong = [], 0
    for kind, r in pick_served(reqs, {r["rid"]: r["shared"] for r in items},
                               reach):
        gap = token_gap(refs["long"], r, vocab)
        wrong += gap > TOKEN_GAP
        checked.append((r.rid, kind, len(r.prompt), len(r.out_tokens), gap))
    served_enough = [k for _, k, *_ in checked] == [
        "past_window", "behind_system_prompt"]
    # The toy's margins are another distribution (8 experts, top-3).
    agree = logits_agree(rows, 0 if ctx.rehearsal else LIKELY_MIN)
    controls_fail = {name: not logits_agree(c)
                     for name, c in controls.items()}
    b = facts["system_prompt_blocks"]
    resumed = facts["shared"]["resumed_at"] == b \
        and set((facts["shared"]["cached_by_class"] or {}).values()) == {b}
    slid = all(facts[g]["returned_by_prefill"].get("window", 0) > 0
               for g in ("slid", "long"))
    returned_in_window = \
        (snapshot.get("prefill_window_blocks_returned") or 0) > 0
    correct = s["failed"] == 0 and wrong == 0 and served_enough \
        and agree and all(controls_fail.values()) and resumed and slid \
        and returned_in_window and all(system_cached) \
        and compiles_window == 0 and s["output_tokens"] > 0
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, logits_agree=agree,
            logit_summary=logit_summary(rows),
            controls={name: logit_summary(c) for name, c in controls.items()},
            controls_fail=controls_fail, facts=facts, resumed=resumed,
            slid_during_prefill=slid,
            limits={"clean": LOGIT_ATOL, "median": MEDIAN_ATOL,
                    "flipped": FLIP_ATOL, "margin_decided": MARGIN_DECIDED,
                    "margin_likely": MARGIN_LIKELY,
                    "likely_clean": LIKELY_CLEAN, "likely_min": LIKELY_MIN,
                    "group_clean": GROUP_CLEAN, "token_gap": TOKEN_GAP},
            served_tokens_checked=checked, paged_kernel=paged_kernel,
            max_slots=max_slots, prefill_chunk=prefill_chunk, kv=kv,
            memory_peak_bytes_at_window_end=peak_window,
            offered_tokens_per_s=sum(r.max_new_tokens for r in reqs)
            / ctx.seconds, snapshot=snapshot, **s)

    record = {
        "kind": "serve", "correct": correct, "attempted": s["attempted"],
        "failed": s["failed"] + wrong,
        "end_to_end": {"serve_tokens_per_s": s["tokens_per_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "summary": s, "snapshot": snapshot, "kv": kv,
        "smallthinker": {k: ctx.config[k] for k in smallthinker_costs.KEYS},
        "prefill_chunk": prefill_chunk,
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

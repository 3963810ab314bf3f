"""kind: reason -- short prompts answered with replies of thousands of
tokens, from a standing backlog, through ``InferenceEngine.serve``, for a
configuration of the ``deepseek_v3`` family on several residual streams
(``model_type: xing4_0``: manifold-constrained hyper-connections).

Set-up (outside the window): bf16 weights (fp32 residual maps) from the
seed on the device, one engine, throw-away requests that compile the
prefill chunk, the decode step and the copy-on-write block copy; the
float32 reference comparison; ``reset_serving_stats()``.  Window:
``backlog`` requests due at 0 and an open loop over ``[0, --seconds)`` at
the traffic file's fixed rate, above what the system sustains, cut by the
scheduler at the window's end: decode of ~250 streams over short, GROWING
contexts plus the prompts' prefill.  After the window: every emitted token
of two requests served inside the full batch against the reference.

``correct`` (decided on the chip at the published widths, from what the
timed path produced; logits, not tokens), every part of it:
1. prefill and first-decode logits through the paged latent cache against
   the reference's full forward (``lib/xing_reference.py``) for ``N_SHORT``
   unshared prompts of ``SHORT_LEN`` tokens and for one unshared prompt of
   ``LONG_LEN`` tokens (four chunks, then its first decode), ``2 * N_SHORT
   + 2`` positions: their MEDIAN error within ``MEDIAN_ATOL``; every
   position whose routing the reference finds DECIDED (margin >=
   ``MARGIN_EPS``) within ``LOGIT_ATOL``; at most ``MAX_FLIPPED`` positions
   over ``LOGIT_ATOL`` at all, and those within ``FLIP_ATOL``
   (``logits_agree``).  Three CONTROLS are held to the same rule over the
   short prompts every run and must each FAIL it: the reference with 8-bit
   (e4m3) operands, with ``H_res`` = I (the streams never mix), and with
   one Sinkhorn iteration of the twenty;
2. every emitted token of two requests served inside the full batch — the
   latest-started one with at least ``LONG_REPLY`` tokens emitted and
   positions past ``PAST`` (blocks grown during decode), and the
   latest-started one behind a shared system prompt with ``SHORT_REPLY``
   or more — against the reference's largest logit in its teacher-forced
   forward: none further below it than ``TOKEN_GAP_MAX``, and at most
   ``TOKEN_SHARE`` of a request's tokens further than ``TOKEN_GAP``
   (``tokens_agree``);
3. no request over its length, zero compiles in the window, some output.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

# The parent of this runner's PR has no such module: the cell fails there
# at once, before a device is touched, and never serves the model without
# its maps (``DeepseekV3Config.from_hf`` drops keys it does not name).
from deepspeed_tpu.models import hyper_connections  # noqa: F401
from perfbench.lib import reason_traffic, traffic as traffic_lib, xplane
from perfbench.lib import xing_reference as reference
from perfbench.runners import _common, serve as serve_runner

# Served logits (bf16 weights, bf16 residual streams, fp32 maps and mixes,
# fp32 accumulation, absorbed attend over the paged bf16 latent cache)
# against the float32 reference on the same weights upcast.  Logits of the
# randomly initialised model have a standard deviation of about 1.2 (a
# unit-RMS final norm x a 3,584-wide head at std 0.02).  Read on the chip
# over 21 seeds x 22-42 positions = 862 (my chip runs, PR 41 c1-c3; PERF.md
# section 2).  Two kinds of difference, as in ``runners/docqa.py``:
# - rounding: 0.038-0.156 wherever no routing decision flipped, median of a
#   run 0.066-0.087.  The three controls read, as a run's median over 40
#   positions: the reference with 8-bit (e4m3) operands 3.0-3.9, with H_res =
#   I 1.95-2.7, with one Sinkhorn iteration 1.18-1.92, and each of them over
#   0.4 at 35 of 40 positions or more.  MEDIAN_ATOL 0.2 lies 2.3x above the
#   one reading and 6x below the least of the others.
# - a flipped routing decision: top-4 of 64 in ONE group is discrete and its
#   margins are small (the 4th and 5th of 64 candidates a layer, the least
#   over five layers: a third of the positions read under 0.002); where the
#   margin is under the rounding of the router's input the served path
#   chooses the other expert, and one swapped expert of four (weight ~0.5)
#   moves the logits by 0.42-3.39, median 1.55: 19% of the positions (3-11 of
#   a run's 42).  By the reference's margin (c1 / c2, 316 positions): flips
#   at 16 / 14 of 30 / 30 positions under 0.0005 / 0.001, 17 of 60 in
#   0.001-0.002, 10 of 60 in 0.002-0.004, 3 of 41 in 0.004-0.006, 0 of 95
#   above (largest error there 0.147).  So a position is DECIDED from 0.012
#   up (twice the largest margin a flip was read at; about 4 of 42 a run)
#   and held to LOGIT_ATOL 0.4 (2.5x the largest reading without a flip,
#   under the least with one); of ALL positions at most MAX_FLIPPED 20 of 42
#   may exceed it (read 3-11; Binomial(42, 0.19) passes 20 once in 1e5 runs;
#   every control reads 35 of 40 or more) and none FLIP_ATOL 6.0 (the
#   largest of ~170 flips read 3.39; logits that share nothing differ by
#   ~7.6 somewhere in 131,072).
# - an emitted token: where the served logits are within e of the
#   reference's, the token lies within 2e of the reference's largest logit.
#   Read over 40 requests of 66-1,262 tokens: over 1.0 at 0-3.2% of a
#   request's tokens; the largest gap of a request 0.40-3.54, and once 5.64
#   (c3, one token of 1,148: the first rule of this file refused that run at
#   5.0).  A token from a wrong slot or a stale row is a random one: 5.4
#   below the largest of 131,072 (sd 1.25), over 1.0 every time — so ONE
#   token cannot be told from a flip's aftermath by its gap, a request's
#   SHARE can: at most TOKEN_SHARE 10% of its tokens over TOKEN_GAP 1.0, on
#   requests of SHORT_REPLY 256 tokens or more (10% of 256 is 26 where 3.2%
#   is 8), and none over TOKEN_GAP_MAX 8.0 (above twice the largest flip
#   read, 6.8; a masked row or a NaN reads no number at all).
MEDIAN_ATOL = 0.2
LOGIT_ATOL = 0.4
FLIP_ATOL = 6.0
MARGIN_EPS = 0.012
MAX_FLIPPED = 20
TOKEN_GAP = 1.0
TOKEN_SHARE = 0.10
TOKEN_GAP_MAX = 8.0
N_SHORT = 20
SHORT_LEN = 600
LONG_LEN = 2048
LONG_REPLY = 1000
PAST = 2000
SHORT_REPLY = 256
CONTROLS = {"8bit": dict(cast=jnp.float8_e4m3fn),
            "res_identity": dict(fault="res_identity"),
            "sinkhorn_once": dict(fault="sinkhorn_once")}
SPANS = serve_runner.SPANS


def model_config(sizes: dict):
    """The program's DeepseekV3Config from the configuration file: the
    published keys as published (the five ``hc_*`` / ``mhc_*`` among
    them), every expert and vocabulary row held."""
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config
    cfg = DeepseekV3Config.from_hf(
        sizes, initializer_range=float(sizes["assumed"]["initializer_range"]),
        max_position_embeddings=int(sizes["max_position_embeddings"]))
    assert cfg.hc_mult == int(sizes["hc_mult"]) and cfg.hyper is not None
    assert cfg.held == (0, int(sizes["n_routed_experts"]))
    assert cfg.vocab_rows == int(sizes["assumed"]["vocab_rows_held"])
    return cfg


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.deepseek_v3 import deepseek_v3_init
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: deepseek_v3_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def _padded(tokens, width: int, out_positions, n_out: int):
    row = np.zeros(width, np.int32)
    row[:len(tokens)] = tokens
    out = np.zeros(n_out, np.int32)
    out[:len(out_positions)] = out_positions
    return jnp.asarray(row), jnp.asarray(out)


def _reference(params, sizes, width: int, q_block: int, **variant):
    """One compiled reference for token rows padded to ``width`` (causal:
    padding after the real tokens changes nothing before it) and two
    output positions: (logits, routing margin)."""
    fn = jax.jit(lambda p, t, out: reference.forward(
        p, t, sizes, out_positions=out, q_block=q_block, **variant))

    def run(tokens, out_positions):
        lg, margin = fn(params, *_padded(tokens, width, out_positions, 2))
        return np.asarray(lg), np.asarray(margin)
    return run


def _token_gaps(params, sizes, width: int, n_out: int, vocab: int):
    """One compiled teacher-forced reference for rows padded to ``width``:
    per emitted token, the reference's largest logit minus that token's."""
    def gaps(p, t, out, nxt):
        h, _ = reference.hidden(p, t, sizes, out_positions=out, q_block=128)
        return reference.token_gaps(
            p, h, nxt, vocab=vocab,
            v_block=min(8192, p["lm_head"].shape[0]))
    fn = jax.jit(gaps)

    def run(prompt, out_tokens):
        plen, n = len(prompt), len(out_tokens)
        toks = np.concatenate([prompt, np.asarray(out_tokens, np.int32)])
        row, out = _padded(toks, width, np.arange(plen - 1, plen + n - 1),
                           n_out)
        nxt = np.zeros(n_out, np.int32)
        nxt[:n] = out_tokens
        return np.asarray(fn(params, row, out, jnp.asarray(nxt)))[:n]
    return run


def _through_the_cache(engine, prompt):
    """(first token, [prefill logits, first-decode logits]) of ``prompt``
    served alone through the engine's own admission, prefill and decode."""
    slot = engine.select_slot(prompt, 2)
    tok, pre = engine.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=2)
    engine.activate_slot(slot, len(prompt), tok)
    _, dec = engine.decode_once(return_logits=True)
    engine.release_slot(slot)
    return tok, np.stack([np.asarray(pre, np.float32),
                          np.asarray(dec[slot], np.float32)])


def check_against_reference(engine, sizes, vocab: int, seed: int):
    """(rows [(what, tokens, |logit error| max, margin)] per checked
    position, {control: rows of the same form over the short prompts})."""
    rng = np.random.default_rng([seed, 3])
    short = min(SHORT_LEN, engine.max_len // 4)
    long_ = min(LONG_LEN, engine.max_len // 2)
    params = engine._params
    ref_short = _reference(params, sizes, short + 1, 256)
    ref_long = _reference(params, sizes, long_ + 1, 128)
    rows, seen = [], []

    def compare(name, got, want, margin, n):
        return [(f"{name}.{what}", n,
                 float(np.abs(got[j, :vocab] - want[j, :vocab]).max()),
                 float(margin[j])) for j, what in enumerate(("prefill",
                                                             "decode"))]
    for i in range(N_SHORT):
        prompt = rng.integers(0, vocab, size=short, dtype=np.int32)
        tok, got = _through_the_cache(engine, prompt)
        toks = np.concatenate([prompt, [tok]])
        want, margin = ref_short(toks, [short - 1, short])
        seen.append((toks, got))
        rows += compare(f"short{i}", got, want, margin, short)
    prompt = rng.integers(0, vocab, size=long_, dtype=np.int32)
    tok, got = _through_the_cache(engine, prompt)
    want, margin = ref_long(np.concatenate([prompt, [tok]]),
                            [long_ - 1, long_])
    rows += compare("long", got, want, margin, long_)
    # The controls: what a wrong model, or the nearest precision below the
    # stated one, reads against the SERVED logits under the same rule.
    controls = {}
    for name, variant in CONTROLS.items():
        ref = _reference(params, sizes, short + 1, 256, **variant)
        controls[name] = []
        for i, (toks, got) in enumerate(seen):
            want, margin = ref(toks, [short - 1, short])
            controls[name] += compare(f"short{i}", got, want, margin, short)
    return rows, controls


def logits_agree(rows) -> bool:
    errs = sorted(r[2] for r in rows)
    return errs[len(errs) // 2] <= MEDIAN_ATOL \
        and all(r[2] <= LOGIT_ATOL for r in rows if r[3] >= MARGIN_EPS) \
        and sum(e > LOGIT_ATOL for e in errs) <= MAX_FLIPPED \
        and errs[-1] <= FLIP_ATOL


def pick_served(reqs, shared_of, long_reply: int, past: int,
                short_reply: int):
    """The two requests whose every emitted token is checked: the
    latest-started with a long reply that grew past ``past`` positions, and
    the latest-started other one behind a shared system prompt."""
    started = sorted((r for r in reqs if r.t_first is not None
                      and len(r.out_tokens) >= short_reply),
                     key=lambda r: r.t_first)
    grown = [r for r in started if len(r.out_tokens) >= long_reply
             and len(r.prompt) + len(r.out_tokens) > past]
    picked = grown[-1:]
    shared = [r for r in started if shared_of[r.rid] >= 0
              and r not in picked]
    return picked + shared[-1:]


def tokens_agree(gap) -> bool:
    return float(gap.max()) <= TOKEN_GAP_MAX \
        and float((gap > TOKEN_GAP).mean()) <= TOKEN_SHARE


def run(ctx):
    tr = ctx.traffic
    sizes = dict(ctx.config, held=(0, int(ctx.config["n_routed_experts"])))
    vocab = int(ctx.config["vocab_size"])
    cfg, engine = build_engine(ctx)
    ctx.mark("weights_and_engine")
    serve_runner.warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)

    rows, controls = check_against_reference(engine, sizes, vocab, ctx.seed)
    ctx.mark("reference")
    engine.reset_serving_stats()
    items = reason_traffic.requests(tr, ctx.seed, ctx.seconds, vocab)
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], backlog=tr["backlog"])

    tracer = None
    if ctx.trace:
        import threading
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
    reclaimed0 = engine.allocator.reclaimed
    setup_s = time.perf_counter() - ctx.t0
    if tracer:
        tracer.start()
    reqs, report, wall, live = serve_runner.measure(engine, items,
                                                    ctx.seconds)
    if tracer:
        tracer.join()
    compiles_window = int(ctx.compile_events.get("n", 0))

    s = serve_runner.summarize(reqs, wall)
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "model_counters")}
    half = live[len(live) // 2:]
    kv = {"num_blocks": engine.num_blocks,
          "block_bytes": engine.allocator.spec.block_nbytes(),
          "live_blocks_mean": float(np.mean(half)) if half else None,
          "live_blocks_max": max(live, default=None),
          "live_blocks_by_second": live,
          "reclaimed_in_window": engine.allocator.reclaimed - reclaimed0}
    peak_window = _common.memory_peak_bytes(ctx.devices)

    # The teacher-forced rows are up to max_total wide and float32 on four
    # streams: the pool has done its work and makes room for them.
    params = engine._params
    engine.cache.clear()
    picked = pick_served(reqs, {r["rid"]: r["shared"] for r in items},
                         min(LONG_REPLY, tr["output_len"]["max"] // 2),
                         min(PAST, tr["max_total"] // 3),
                         min(SHORT_REPLY, tr["output_len"]["min"]))
    gaps_of = _token_gaps(params, sizes, -(-tr["max_total"] // 128) * 128,
                          tr["output_len"]["max"], vocab)
    served, wrong = [], 0
    for r in picked:
        gap = gaps_of(r.prompt, r.out_tokens)
        wrong += not tokens_agree(gap)
        served.append((r.rid, len(r.prompt), len(r.out_tokens),
                       float(gap.max()), float((gap > TOKEN_GAP).mean()),
                       float(np.percentile(gap, 99))))
    controls_fail = {name: not logits_agree(c)
                     for name, c in controls.items()}
    correct = s["failed"] == 0 and wrong == 0 and len(served) == 2 \
        and logits_agree(rows) and all(controls_fail.values()) \
        and compiles_window == 0 and s["output_tokens"] > 0
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, logits_agree=logits_agree(rows),
            control_checks={n: [r[2] for r in c]
                            for n, c in controls.items()},
            controls_fail=controls_fail,
            limits={"median": MEDIAN_ATOL, "decided": LOGIT_ATOL,
                    "flipped": FLIP_ATOL, "margin": MARGIN_EPS,
                    "max_flipped": MAX_FLIPPED, "token_gap": TOKEN_GAP,
                    "token_share": TOKEN_SHARE,
                    "token_gap_max": TOKEN_GAP_MAX},
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
        "summary": s, "snapshot": snapshot, "kv": kv,
        "latent": {k: ctx.config[k] for k in (
            "kv_lora_rank", "qk_rope_head_dim", "num_attention_heads",
            "num_hidden_layers", "first_k_dense_replace", "hidden_size",
            "moe_intermediate_size", "n_routed_experts")},
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

"""kind: docqa_state -- short questions over long cached documents through
``InferenceEngine.serve``, for a configuration of the ``brumby`` family:
a model whose cache is a fixed-size STATE a stream, and whose prefix cache
is SNAPSHOTS of it.  ``runners/docqa.py``'s twin; the traffic generator,
the measurement, the summary and the after-window token check are that
runner's and ``runners/serve.py``'s, imported.

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile the prefill chunk and the decode
step; EVERY DOCUMENT SERVED ONCE (1 new token) through ``engine.serve`` so
that its snapshot sits in the prefix cache (which compiles the page copy);
the float32 reference comparison; ``reset_serving_stats()``.  Window:
arrivals over ``[0, --seconds)`` at the traffic file's fixed rate, above
what the system sustains, cut by the scheduler at the window's end; each
request = a cached document + an unshared question: a page copy, a prefill
of the question from the snapshot's position, then decode whose cost does
not grow with the document.  After the window: emitted tokens of requests
served inside the full batch against the reference, and every document's
snapshot still in the prefix cache.

``correct`` (decided on the chip at the published widths, from what the
timed path produced; logits, not tokens), every part of it:
1. prefill and first-decode logits through the state pool against the
   reference's full forward (``lib/brumby_reference.py``: the quadratic
   form) for ``N_SHORT`` unshared prompts of ``SHORT_LEN`` tokens and for
   a question over the SHORTEST document through the snapshot-hit path
   (~10.7k positions), whose stream then decodes a REPLY of
   ``REPLY_STEPS`` tokens: every one of the ``2 * N_SHORT + 3`` positions
   (the reply's last too) within ``LOGIT_ATOL``, and the question's
   prefill must have RESUMED from the document's snapshot;
2. LAYER 0 of the page that stream holds after the reply against the
   page it held before it (right after prefill), carried over the reply's
   tokens by ``reference.carry_state`` in float32 with the keys, values
   and gates that the PROGRAM's own embedding, norm and projections give
   (layer 0's depend on nothing the retention does, so what is left
   between the two is the state's own arithmetic over ``REPLY_STEPS``
   in-place updates), both pages read through the program's
   ``pair_tensor``: every K/V head's values within ``VALUES_RTOL`` and
   normaliser within ``NORMALISER_RTOL`` (relative, Frobenius) — the
   comparison that sees the precision the state is HELD in, which logits
   four layers of bf16 rounding away do not;
3. the yardstick's controls, every run, through the same two comparisons:
   the reference with 8-bit operands must FAIL 1, the reference's state
   carried over the reply in bfloat16 must FAIL 2;
4. every emitted token of the latest-started request on the shortest
   document and of one more on the second-shortest, both served inside
   the full batch, within ``TOKEN_GAP`` of the reference's largest logit
   in its teacher-forced forward;
5. no request over its length, zero compiles in the window, no snapshot
   reclaimed in the window and every document's still matched, some
   output.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import brumby_reference as reference
from perfbench.lib import docqa_traffic, traffic as traffic_lib, xplane
from perfbench.runners import _common, serve as serve_runner
from perfbench.runners.docqa import _through_the_cache, check_served_tokens

# Served logits (bf16 weights and activations; fp32 gate, state, normaliser,
# norms and accumulation; the chunked form in prefill, the recurrent kernel
# in decode, a snapshot copied page to page) against the float32 QUADRATIC
# reference on the same bf16 weights upcast.  Logits of the randomly
# initialised model have a standard deviation of about 1.4 (unit-RMS final
# norm x a 5120-wide head at std 0.02).  The only difference is rounding:
# the bf16 residual stream and projections of four layers, q / k / v rounded
# to bf16 before the feature map.  Read on the chip (my chip runs, PR 34,
# PERF.md section 2): the served path 0.094-0.135 at each of 10 positions of
# 9 seeds (short prompts and the question at 10,656 positions alike; prefill
# and decode alike); the reference with 8-bit (e4m3) operands 1.90-1.93
# (printed every run: ``logit_abs_err_8bit_reference``); the reference with
# power 1 / no gate / no normaliser / un-rotated keys 9.5 / 7.9 / 9.1 / 8.7;
# the served path resuming from ANOTHER document's snapshot 9.2, from a
# zeroed one 9.8.  LOGIT_ATOL 0.3 is 2.2x above the largest reading of the
# one and 6x below the least of the others.  An emitted token lies within
# 2 x LOGIT_ATOL of the reference's largest logit (both logits are within
# LOGIT_ATOL; read 0-0.034); one from a wrong slot or a stale state is a
# random token, about 5.5 below.
LOGIT_ATOL = 0.3
TOKEN_GAP = 2 * LOGIT_ATOL
# Layer 0 of the page after a reply, against the page before it carried
# over the reply by the reference's recurrence with the program's own
# layer-0 keys, values and gates (relative error of a K/V head's values /
# normaliser, Frobenius).  Both sides then add the same terms to the same
# state and differ by rounding alone.  Read on the chip (my chip runs, PR
# 34 c10, PERF.md section 2): the served path 0.8e-7 to 1.3e-7 on the
# values and 0.0, bit for bit, on the normaliser; the served path with its
# state rounded to bfloat16 at every write 0.9e-2 to 2.7e-2 on the values
# and 0.9e-2 to 2.6e-2 on the normaliser, EVERY head, and the reference's
# own bf16-carried control the same to six digits (printed every run, and
# it has to fail).  A bfloat16 state rounds the WHOLE state at every token
# (2^-9 of it a step, random), and the normaliser's diagonal, a sum of
# squares that grows like the length, stands still (one token's square is
# under half an ulp).  VALUES_RTOL leaves room for one thing that is no
# fault: the compiler keeps excess precision, so the decode program hands
# the kernel the value projection's float32 accumulator, and a compiler
# that rounded v to bf16 in one of the two programs only would read
# 1.7e-3 on a head that forgot everything older (c9's decode-only
# reading).  The normaliser sees keys and gates only and has no such
# case: its limit is four times tighter.
VALUES_RTOL = 2.0 ** -8
NORMALISER_RTOL = 2.0 ** -10
REPLY_STEPS = 256
N_SHORT = 4
SHORT_LEN = 600
Q_BLOCK = 128
SPANS = serve_runner.SPANS


def model_config(sizes: dict):
    """The program's BrumbyConfig from the configuration file: the
    published keys as published, the assumed ones from ``assumed``."""
    from deepspeed_tpu.models.brumby import BrumbyConfig
    assumed = sizes["assumed"]
    return BrumbyConfig.from_hf(
        sizes, **{k: assumed[k] for k in (
            "retention_power", "retention_eps", "initializer_range",
            "gate_half_life_min", "gate_half_life_max")})


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.brumby import brumby_init
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config)
    params = jax.jit(lambda key: brumby_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def _reference(engine, sizes, width: int, n_out: int, cast=None):
    """One compiled reference for token rows padded to ``width`` and
    ``n_out`` output positions; returns (logits, None) as
    ``runners/docqa.py``'s does (a margin has no meaning here)."""
    fn = jax.jit(lambda p, t, out: reference.forward(
        p, t, sizes, out_positions=out, q_block=Q_BLOCK, cast=cast))

    def run(tokens, out_positions):
        row = np.zeros(width, np.int32)
        row[:len(tokens)] = tokens
        out = np.zeros(n_out, np.int32)
        out[:len(out_positions)] = out_positions
        lg = fn(engine._params, jnp.asarray(row), jnp.asarray(out))
        return np.asarray(lg)[:len(out_positions)], None
    return run


def _width(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def _page_layer0(engine, slot):
    """A copy of layer 0 of ``slot``'s page as held: (state, norm)."""
    g, page = engine.group_of(slot), int(engine.block_tables[slot][0])
    return jax.jit(lambda S, z: (S[0, g, page], z[0, g, page]))(
        engine.cache["state"], engine.cache["norm"])


def _reply_through_the_cache(engine, prompt, steps: int):
    """``_through_the_cache`` with a reply: the stream decodes ``steps``
    tokens (greedy) and is left LIVE in its slot.  Returns (slot, the
    tokens the cache then holds, [prefill, first-decode, last-decode
    logits], admission, layer 0 of its page as it was right after
    prefill)."""
    slot = engine.select_slot(prompt, steps + 1)
    tok, pre = engine.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=steps + 1)
    info = dict(engine.last_admit_info(slot))
    before = _page_layer0(engine, slot)
    engine.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre, np.float32)]
    for i in range(steps):
        ask = i in (0, steps - 1)
        sampled, dec = engine.decode_once(return_logits=ask)
        if ask:
            got.append(np.asarray(dec[slot], np.float32))
        toks.append(int(sampled[slot]))
    return slot, np.concatenate([prompt, toks[:steps]]).astype(np.int32), \
        np.stack(got), info, before


def state_errors(engine, slot, before, held, reply: int):
    """[(K/V head, values' error, normaliser's error, the same two of the
    bf16 control)]: layer 0 of ``slot``'s page against its layer 0
    ``before`` the reply carried over the last ``reply`` tokens of ``held``
    by the reference in float32, and against that the same carried in
    bfloat16."""
    from deepspeed_tpu.models import brumby
    from deepspeed_tpu.ops.power_retention import pair_tensor
    cfg = engine.model_cfg

    def rel(a, b):                         # [c, D + 1, D, D] each -> [c, 2]
        def norm(x):
            return jnp.sqrt(jnp.stack([jnp.square(x[:, :-1]).sum((1, 2, 3)),
                                       jnp.square(x[:, -1]).sum((1, 2))],
                                      axis=-1))
        return norm(a - b) / norm(b)

    @jax.jit
    def errors(params, before, after, tokens, positions):
        # Layer 0's keys, values and gates as the program computes them.
        p = {name: w[0] for name, w in params["layers"].items()}
        x = params["embed"].astype(cfg.dtype)[tokens]
        _, k, v, log_g = brumby.retention_projections(
            p, brumby.rms_norm(x, p["input_norm"], cfg.rms_norm_eps),
            positions, cfg)
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        start = pair_tensor(*before)
        want = reference.carry_state(start, k, v, log_g)
        low = reference.carry_state(start, k, v, log_g, cast=jnp.bfloat16)
        return rel(pair_tensor(*after), want), rel(low, want)

    n = len(held) - reply
    served, low = (np.asarray(e) for e in errors(
        engine._params, before, _page_layer0(engine, slot),
        jnp.asarray(held[n:]), jnp.arange(n, len(held))))
    return [(c, float(served[c, 0]), float(served[c, 1]),
             float(low[c, 0]), float(low[c, 1]))
            for c in range(served.shape[0])]


def check_against_reference(engine, sizes, docs, vocab: int, seed: int,
                            ref_long, question_len: int, reply: int):
    """([(what, tokens resumed from a snapshot, |logit error| max)] per
    checked position, ``state_errors``'s rows, the 8-bit control's rows)."""
    rng = np.random.default_rng([seed, 3])
    short = min(SHORT_LEN, engine.max_len // 2)
    ref_short = _reference(engine, sizes, _width(short + 1), 2)
    rows, first = [], None
    for i in range(N_SHORT):
        prompt = rng.integers(0, vocab, size=short, dtype=np.int32)
        tok, got, info = _through_the_cache(engine, prompt)
        toks = np.concatenate([prompt, [tok]])
        want, _ = ref_short(toks, [short - 1, short])
        first = first or (toks, want)
        for j, what in enumerate(("prefill", "decode")):
            rows.append((f"short{i}.{what}", info.get("cached_tokens", 0),
                         float(np.abs(got[j] - want[j]).max())))
    q = rng.integers(0, vocab, size=question_len, dtype=np.int32)
    prompt = np.concatenate([docs[0], q])
    slot, held, got, info, before = _reply_through_the_cache(
        engine, prompt, reply)
    n = len(prompt)
    want, _ = ref_long(held, [n - 1, n, len(held) - 1])
    for j, what in enumerate(("prefill", "decode", "reply")):
        rows.append((f"doc0.{what}", info.get("cached_tokens", 0),
                     float(np.abs(got[j] - want[j]).max())))
    pages = state_errors(engine, slot, before, held, reply)
    engine.release_slot(slot)
    # What the nearest precision below the stated one reads: every
    # product's operands rounded to 8 bits (e4m3) first.  It judges the
    # yardstick, not the system: it has to FAIL ``logits_agree``.
    low, _ = _reference(engine, sizes, _width(short + 1), 2,
                        cast=jnp.float8_e4m3fn)(first[0], [short - 1, short])
    return rows, pages, [
        (f"short0.{what}.8bit_reference", 0,
         float(np.abs(low[j] - first[1][j]).max()))
        for j, what in enumerate(("prefill", "decode"))]


def logits_agree(rows, doc_boundary: int) -> bool:
    """Every position within LOGIT_ATOL, and the long one through the
    snapshot (resumed at the document's last block boundary)."""
    return all(err <= LOGIT_ATOL for _, _, err in rows) \
        and all(resumed == doc_boundary for what, resumed, _ in rows
                if what.startswith("doc0"))


def state_agrees(errors) -> bool:
    """Every head's values within VALUES_RTOL and normaliser within
    NORMALISER_RTOL (``errors``: [(values' error, normaliser's error)])."""
    errors = list(errors)
    return bool(errors) and all(
        values <= VALUES_RTOL and normaliser <= NORMALISER_RTOL
        for values, normaliser in errors)


def run(ctx):
    tr = ctx.traffic
    sizes = ctx.config
    vocab = int(sizes["vocab_size"])
    cfg, engine = build_engine(ctx)
    bs = engine.block_size
    ctx.mark("weights_and_engine")
    serve_runner.warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)

    docs = docqa_traffic.documents(tr, ctx.seed, vocab)
    engine.serve(serve_runner._requests([
        {"rid": -100 - i, "prompt": d, "max_new_tokens": 1, "arrival_s": 0.0}
        for i, d in enumerate(docs)]))
    boundaries = [len(d) // bs * bs for d in docs]

    def snapshots_matched():
        # A question's first token stands in for the question: the longest
        # snapshot boundary it would resume from.
        return [engine.prefix_match_tokens(np.concatenate([d, [0]])) == b
                for d, b in zip(docs, boundaries)]
    ctx.mark("documents")

    longest = max(len(docs[0]), len(docs[min(1, len(docs) - 1)])) \
        + tr["question_len"]["max"] + tr["output_len"]["max"]
    reply = min(REPLY_STEPS, int(tr["output_len"]["max"]))
    ref_long = _reference(engine, sizes, _width(longest),
                          tr["output_len"]["max"])
    rows, pages, rows_8bit = check_against_reference(
        engine, sizes, docs, vocab, ctx.seed, ref_long,
        int(tr["question_len"]["median"]), reply)
    ctx.mark("reference")
    engine.reset_serving_stats()
    items = docqa_traffic.requests(tr, ctx.seed, ctx.seconds, vocab, docs)
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], documents=len(docs),
            document_tokens=int(sum(len(d) for d in docs)),
            state_page_bytes=engine.allocator.spec.block_nbytes(),
            state_page_tokens=engine.allocator.spec.page_tokens)

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
    matched = snapshots_matched()
    reclaimed = engine.allocator.reclaimed - reclaimed0
    served = check_served_tokens(
        reqs, {r["rid"]: r["shared"] for r in items}, docs, vocab, ref_long)
    wrong = sum(gap > TOKEN_GAP for _, _, gap in served)
    agree = logits_agree(rows, boundaries[0])
    state_ok = state_agrees(r[1:3] for r in pages)
    # The controls have to fail the comparisons the system has to pass.
    controls = {"logits_8bit_reference": not logits_agree(rows_8bit, 0),
                "state_bf16_reference": not state_agrees(
                    r[3:5] for r in pages)}
    correct = s["failed"] == 0 and wrong == 0 and len(served) > 0 \
        and agree and state_ok and all(controls.values()) \
        and compiles_window == 0 and all(matched) \
        and reclaimed == 0 and s["output_tokens"] > 0
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "state")}
    half = live[len(live) // 2:]
    kv = {"num_blocks": engine.num_blocks,
          "block_bytes": engine.allocator.spec.block_nbytes(),
          "live_blocks_mean": float(np.mean(half)) if half else None,
          "live_blocks_max": max(live, default=None),
          "live_blocks_by_second": live,
          "reclaimed_in_window": reclaimed,
          "documents_whole": int(sum(matched))}
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, logits_agree=agree,
            state_checks=pages, state_agrees=state_ok,
            limits={"logit_atol": LOGIT_ATOL, "token_gap": TOKEN_GAP,
                    "values_rtol": VALUES_RTOL,
                    "normaliser_rtol": NORMALISER_RTOL,
                    "reply_steps": reply},
            controls_fail=controls, logit_checks_8bit_reference=rows_8bit,
            logit_abs_err_8bit_reference=max(r[2] for r in rows_8bit),
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
        "summary": s, "snapshot": snapshot, "kv": kv,
        "retention": {k: sizes[k] for k in (
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim")},
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

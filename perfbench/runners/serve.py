"""kind: serve -- open-loop requests through ``InferenceEngine.serve``
(the continuous-batching scheduler over the paged cache), cut from
``chip_smoke.phase_serve``.

Set-up (outside the window): bf16 weights from the seed on the device,
one engine, a handful of throw-away requests that compile the prefill
chunk, the decode step and the copy-on-write block copy, the float32
reference comparison, ``reset_serving_stats()``.  Window: arrivals over
``[0, --seconds)`` at the traffic file's fixed rate, above what the
system sustains, cut by the scheduler at the window's end (the backlog
of an overloaded server never drains): the rate is what was emitted
inside the window.  After the window: the greedy tokens of requests
served inside the full batch against the reference.  Latencies are the
scheduler's own ``Request`` clocks (TTFT from the time a request was DUE).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import reference, traffic as traffic_lib, xplane
from perfbench.runners import _common

# Served logits (bf16 weights, bf16 activations, fp32 accumulation, paged
# cache) against the float32 reference on the SAME bf16 weights upcast:
# logits of a randomly initialised 36-layer model are O(1), bf16 keeps 8
# mantissa bits, and the error grows with depth as a random walk -- the
# largest of 50k logits lands within a few bf16 ulp of O(1-4) values.
# chip_smoke.LOGIT_ATOL argues 0.25 for kernel against one-hot attention;
# read on the chip here: 0.041-0.049 in 15 runs (PR 23), so 0.15 leaves a
# factor of three; an 8-bit compute type or a skipped layer is off by > 1.
LOGIT_ATOL = 0.15
CHECK_LEN = 512       # one padded shape for the after-window token check
SPANS = ("prefill_many", "decode_once")


def _annotated(name, fn):
    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)
    return wrapped


def _requests(items):
    from deepspeed_tpu.inference.scheduler import Request
    return [Request(rid=r["rid"], prompt=r["prompt"],
                    max_new_tokens=r["max_new_tokens"],
                    arrival_s=r["arrival_s"]) for r in items]


def build_engine(ctx):
    """(model config, engine) on the cell's devices, weights from seed."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = _common.model_config(ctx.config)
    params = _common.seeded_params(cfg, ctx.seed, dtype=cfg.dtype)
    engine = InferenceEngine(
        cfg, params, config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


def warm_up(engine, vocab: int, seed: int):
    """Compile every program the window can reach: chunked prefill,
    decode, and the block copy (an identical prompt of whole blocks served
    twice forks its last block copy-on-write)."""
    rng = np.random.default_rng(seed + 1)
    bs = max(engine.block_size, 1)
    same = rng.integers(0, vocab, size=3 * bs, dtype=np.int32)
    other = rng.integers(0, vocab, size=engine.prefill_chunk + 5,
                         dtype=np.int32)
    items = [{"rid": -1 - i, "prompt": p, "max_new_tokens": 4,
              "arrival_s": 0.0} for i, p in enumerate((same, other))]
    engine.serve(_requests(items))
    engine.serve(_requests([dict(items[0], rid=-9)]))


def check_against_reference(engine, cfg, vocab: int, seed: int, lengths):
    """Prefill and the first decode step through the paged cache against
    the reference's full forward at the same positions; returns the
    largest absolute logit error per checked prompt."""
    rng = np.random.default_rng(seed + 2)
    ref = jax.jit(lambda p, t: reference.logits(
        p, t, num_heads=cfg.num_heads, eps=cfg.layer_norm_eps)[0, -2:])
    errs = []
    for plen in lengths:
        prompt = rng.integers(0, vocab, size=plen, dtype=np.int32)
        slot = engine.select_slot(prompt, 2)
        tok, pre = engine.prefill(prompt, slot, return_logits=True,
                                  max_new_tokens=2)
        engine.activate_slot(slot, plen, tok)
        _, dec = engine.decode_once(return_logits=True)
        engine.release_slot(slot)
        want = np.asarray(ref(engine._params, jnp.asarray(
            np.concatenate([prompt, [tok]])[None])))
        got = np.stack([np.asarray(pre, np.float32),
                        np.asarray(dec[slot], np.float32)])
        errs.append(float(np.max(np.abs(got[:, :vocab] - want[:, :vocab]))))
    return errs


def check_served_tokens(engine, cfg, reqs, shared_of, vocab: int):
    """The window's own outputs against the reference: for the latest
    started request with a shared system prompt and the latest without
    (both served inside the full batch, through the paged cache and, for
    the first, through blocks another request wrote), the reference's
    teacher-forced forward over prompt + emitted tokens.  Every emitted
    token has to be the reference's argmax up to rounding: the served
    logits are within LOGIT_ATOL of the reference's, so the token the
    system picked lies within 2 x LOGIT_ATOL of the reference's largest
    logit (about 5 of 50k tokens of a random model do; a wrong one is
    some 3 below).  Returns [(rid, tokens checked, largest gap)]."""
    width = min(CHECK_LEN, engine.max_len)

    def fits(r):
        return r.t_first is not None and len(r.out_tokens) >= 8 \
            and len(r.prompt) + len(r.out_tokens) <= width
    latest = {}
    for r in sorted((r for r in reqs if fits(r)), key=lambda r: r.t_first):
        latest[shared_of[r.rid] >= 0] = r
    if not latest:
        return []

    @jax.jit
    def ref(p, t):
        lg = reference.logits(p, t, num_heads=cfg.num_heads,
                              eps=cfg.layer_norm_eps)[0, :-1, :vocab]
        nxt = jnp.take_along_axis(lg, t[0, 1:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - nxt
    out = []
    for r in latest.values():
        plen, n = len(r.prompt), len(r.out_tokens)
        toks = np.zeros((1, width), np.int32)
        toks[0, :plen] = r.prompt
        toks[0, plen:plen + n] = r.out_tokens
        gap = np.asarray(ref(engine._params, jnp.asarray(toks)))
        out.append((r.rid, n, float(gap[plen - 1:plen + n - 1].max())))
    return out


def measure(engine, items, seconds: float):
    """Serve ``items`` open-loop, cut at ``seconds``; returns (requests,
    report, wall, live-block samples taken once a second)."""
    reqs = _requests(items)
    live, done = [], threading.Event()

    def sample():
        while not done.wait(1.0):
            live.append(engine.allocator.blocks_in_use())
    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()
    t = time.perf_counter()
    report = engine.serve(reqs, temperature=0.0, max_wall_s=seconds)
    wall = time.perf_counter() - t
    done.set()
    sampler.join()
    return reqs, report, wall, live


def summarize(reqs, wall_s: float) -> dict:
    """The numbers from the scheduler's Request clocks, over every request
    that was due inside the window (``attempted``).  The rate is every
    output token emitted inside the window over the window's real length.
    A request that never got a slot enters the wait and first-token tails
    at the time it had waited when the window was cut (the least it can
    come to), so admitting fewer does not flatter them; the time per
    output token exists only for requests that emitted two tokens or
    more.  Failed: a request that emitted more tokens than it asked for."""
    due = [r for r in reqs if r.arrival_s <= wall_s]
    started = [r for r in due if r.t_first is not None]
    waited = [(wall_s - r.arrival_s) * 1e3 for r in due if r.t_first is None]
    ttft = [r.ttft_s * 1e3 for r in started] + waited
    wait = [r.queue_wait_s * 1e3 for r in started] + waited
    tpot = [r.tpot_s * 1e3 for r in started if r.tpot_s is not None]
    out_tokens = sum(len(r.out_tokens) for r in reqs)

    def pct(vals, q):
        return float(np.percentile(vals, q)) if vals else None
    return {"attempted": len(due), "started": len(started),
            "completed": sum(len(r.out_tokens) == r.max_new_tokens
                             for r in started),
            "failed": sum(len(r.out_tokens) > r.max_new_tokens for r in reqs),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
            "tpot_p50_ms": pct(tpot, 50), "tpot_p95_ms": pct(tpot, 95),
            "queue_wait_p95_ms": pct(wait, 95),
            "output_tokens": out_tokens,
            "tokens_per_s": out_tokens / wall_s}


def run(ctx):
    tr = ctx.traffic
    vocab = int(ctx.config["vocab_size"])
    cfg, engine = build_engine(ctx)
    ctx.mark("weights_and_engine")
    warm_up(engine, vocab, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)
    errs = check_against_reference(
        engine, cfg, vocab, ctx.seed,
        (min(200, engine.max_len // 2), engine.prefill_chunk + 17))
    ctx.mark("reference")
    engine.reset_serving_stats()
    items = traffic_lib.serve_requests(tr, ctx.seed, ctx.seconds, vocab)
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"])

    tracer = None
    if ctx.trace:
        engine.prefill_many = _annotated("prefill_many", engine.prefill_many)
        engine.decode_once = _annotated("decode_once", engine.decode_once)

        def traced_window():
            time.sleep(ctx.seconds * float(tr["trace_at_fraction"]))
            _common.start_trace(ctx.trace_dir)
            time.sleep(float(tr["trace_seconds"]))
            jax.profiler.stop_trace()
        tracer = threading.Thread(target=traced_window, daemon=True)

    compiles_setup = dict(ctx.compile_events)
    ctx.compile_events.clear()
    setup_s = time.perf_counter() - ctx.t0
    if tracer:
        tracer.start()
    reqs, report, wall, live = measure(engine, items, ctx.seconds)
    if tracer:
        tracer.join()
    compiles_window = int(ctx.compile_events.get("n", 0))

    s = summarize(reqs, wall)
    served = check_served_tokens(
        engine, cfg, reqs, {r["rid"]: r["shared"] for r in items}, vocab)
    wrong = sum(gap > 2 * LOGIT_ATOL for _, _, gap in served)
    correct = s["failed"] == 0 and wrong == 0 and len(served) > 0 \
        and max(errs) <= LOGIT_ATOL and compiles_window == 0 \
        and s["output_tokens"] > 0
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s")}
    # Blocks that hold live contexts, once a second; the mean is over the
    # window's second half, when the ramp from an empty server is over.
    half = live[len(live) // 2:]
    kv = {"num_blocks": engine.num_blocks,
          "block_bytes": engine.allocator.spec.block_nbytes(),
          "live_blocks_mean": float(np.mean(half)) if half else None,
          "live_blocks_max": max(live, default=None),
          "live_blocks_by_second": live}
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks,
            compiles_warm_up=compiles_warm, compiles_setup=compiles_setup,
            compiles_window=compiles_window, logit_abs_err=errs,
            logit_atol=LOGIT_ATOL, served_tokens_checked=served,
            paged_kernel=engine.paged_kernel, max_slots=engine.max_slots,
            prefill_chunk=engine.prefill_chunk, kv=kv,
            offered_tokens_per_s=sum(r.max_new_tokens for r in reqs)
            / ctx.seconds, snapshot=snapshot, **s)

    record = {
        "kind": "serve", "correct": correct, "attempted": s["attempted"],
        "failed": s["failed"] + wrong,
        "end_to_end": {"serve_tokens_per_s": s["tokens_per_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "summary": s, "snapshot": snapshot, "kv": kv,
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

"""kind: blockgen -- chat and reasoning requests with a fixed generation
budget, from a standing backlog, through ``InferenceEngine.serve``, for a
configuration of the ``sdar_moe`` family: generation by DIFFUSION OVER
BLOCKS.  A stream's step is a pass over a block of ``block_length``
positions; a block takes ``denoising_steps`` denoise passes and one pass
that commits its K/V; ``serve_tokens_per_s`` counts COMMITTED reply tokens
(not rows, not passes, not the prompt tail that opens a first block).

Set-up (outside the window): bf16 weights from the seed on the device, one
engine, throw-away requests that compile every prefill width, the block
step and the copy-on-write block copy; ``reset_serving_stats()``.  Window:
``backlog`` requests due at 0 and an open loop over ``[0, --seconds)`` at
the traffic file's fixed rate, above what the system sustains, cut by the
scheduler at the window's end (``lib/blockgen_traffic.py``).  IN the
window the runner records, for ``N_STREAMS`` streams at the contexts the
traffic gave them (one of them admitted on a prefix-cache hit, where the
window holds one), consecutive passes from the first pass of a block —
``N_BLOCKS`` whole blocks, each of their denoise passes and their commit —:
the block's input ids, the fp32 logits ``[B, V]`` the TIMED path produced
for it (``engine.on_block_pass``), the block after the pass, and the
stream's tokens before the block.  After the window the float32 reference
(``lib/sdar_reference.py``) recomputes those logits from the stream's
tokens alone: a full forward over prompt + committed reply + the block's
input, no cache.

``correct``, every part of it (logits and the procedure; NOT sampled
trajectories: random weights flip an argmax on rounding):
1. the recorded logits against the reference's at the block's ``B``
   positions, a pass: the positions' MEDIAN error within ``MEDIAN_ATOL``;
   every position within ``LOGIT_ATOL`` but ``MAX_OVER`` of them at most,
   and none over ``FLIP_ATOL`` (``logits_agree``; the reference's routing
   margin at each position is printed beside its error and decides
   nothing: top-8 of 128 is discrete, but the flipped expert is by
   construction the least-weighted of eight, and no flip was read above
   the rounding's own spread);
2. the positions the path unmasked in a recorded pass are the rule applied
   to ITS OWN logits (``lib/sdar_reference.unmask_rule`` on the recorded
   logits; a confidence within ``CONF_RTOL`` of the cut may fall either
   way), a committing pass unmasks nothing and leaves the next block all
   undecided, and every recorded run of passes holds ``N_BLOCKS`` blocks with a
   commit every ``denoising_steps + 1``;
3. the comparison can fail, shown every run on the first recorded stream's
   passes, the reference's wrong models read against the RECORDED logits
   under rule 1: ``8bit`` (every product's operands rounded to e4m3: the
   nearest precision below the stated one) and ``causal`` (``block_length``
   1: the mask of a model of tokens) must each FAIL it;
4. ``N_STREAMS`` streams recorded, no request over its budget (a
   completed one holds exactly its ``gen_length`` tokens), zero compiles in
   the window, some output.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import sdar as sdar_model     # fails at once on a
#          program that has no such family: nothing has run yet
from perfbench.lib import blockgen_traffic, traffic as traffic_lib, xplane
from perfbench.lib import sdar_reference as reference
from perfbench.runners import _common, serve as serve_runner

# Served (bf16 weights, activations and K/V; fp32 norms, softmax, routing,
# confidences and accumulation; the paged cache at the contexts the window
# gave the streams: blocks that start at 264-1,496) against the float32
# reference on the same weights upcast; logits of the seeded model have a
# spread of ~0.9.  Read on the chip (my chip runs, PR 62 c1-c3: thirteen runs
# of thirteen seeds x 96 positions = 1,248, seven of them the committed
# files'; PERF.md section 2):
# - a run's MEDIAN position 0.027-0.033; the controls that must fail it, as a
#   run's median over the first stream's 24 positions: every product's
#   operands rounded to e4m3 0.345-0.708, the mask of a model of tokens
#   (``block_length`` 1) 0.414-0.892.  MEDIAN_ATOL 0.1: 3.0x above the one
#   reading, 3.4x below the least of the others: the limit a control fails by.
# - a run's WORST position 0.104-0.158, none of 1,248 over 0.16 (the 9-15
#   positions a run whose routing margin is 0.02 or more in every layer read
#   0.030-0.036 at most: what is left at the others is near-ties' flips, and
#   they stay inside the rounding's spread); the controls' LEAST position
#   0.320 (e4m3) and 0.383 (causal).  LOGIT_ATOL 0.3: 1.9x above the largest
#   reading, under the least of every control's 24.  Two positions of 96 may
#   exceed it (MAX_OVER: a flip nobody has read yet must not refuse a run; a
#   fault moves a whole pass's four positions, a stream's 24) and none
#   FLIP_ATOL 1.5 (logits that share nothing differ by ~5 somewhere in
#   151,669).
# - the rule on the path's own logits: exact but for a confidence within
#   CONF_RTOL of the pass's cut (fp32 on the device, float64 here).
MEDIAN_ATOL = 0.1
LOGIT_ATOL = 0.3
MAX_OVER = 2
FLIP_ATOL = 1.5
CONF_RTOL = 1e-4
N_STREAMS = 4
N_BLOCKS = 2            # whole blocks a stream is recorded for
WATCH_FROM = 0.25       # of the window: the batch is full by then
Q_BLOCK = 128
CONTROLS = {"8bit": dict(cast=jnp.float8_e4m3fn),
            "causal": dict(block_length=1)}
SPANS = serve_runner.SPANS
SDAR_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
             "num_hidden_layers", "hidden_size", "moe_intermediate_size",
             "num_experts", "num_experts_per_tok", "assumed")


def model_config(sizes: dict, traffic: dict):
    """The program's config from the configuration file (the published keys
    as published, ``assumed``'s block length and mask token) and the
    traffic's step count and rule."""
    dtype = (sizes.get("assumed") or {}).get("compute_dtype")
    return sdar_model.SdarConfig.from_hf(
        sizes, denoising_steps=int(traffic["denoising_steps"]),
        remasking=str(traffic["rule"]),
        **({"dtype": jnp.dtype(dtype)} if dtype else {}))


def build_engine(ctx):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = model_config(ctx.config, ctx.traffic)
    params = jax.jit(lambda key: sdar_model.sdar_init(key, cfg))(
        jax.random.PRNGKey(ctx.seed))
    engine = InferenceEngine(
        cfg, params,
        config={"inference": dict(ctx.config["serve"]["inference"])},
        mesh=build_mesh(devices=list(ctx.devices)))
    return cfg, engine


# One slot's logits ``[B, V]`` of a pass's ``[S, B, V]``: ONE program,
# compiled in set-up (``warm_up``), so the window compiles nothing.
take_logits = jax.jit(lambda logits, slot: logits[slot])


def warm_up(engine, ids: int, seed: int):
    """``serve_runner.warm_up`` (every prefill width, the block step, the
    block copy) with the recorder's one program compiled on a real pass's
    logits."""
    engine.on_block_pass = lambda flight, blocks: take_logits(
        flight.logits, 0)
    serve_runner.warm_up(engine, ids, seed)
    engine.on_block_pass = None


class Recorder:
    """``engine.on_block_pass``: the in-window sample (module docstring).
    One stream is watched at a time, so a pass costs one fetch of ``[B, V]``
    logits at most."""

    def __init__(self, engine, reqs, seconds: float, want_hit: bool):
        self.engine, self.reqs = engine, reqs
        self.n_passes = n_passes(engine.served.cfg)
        self.t0 = time.perf_counter()
        self.after = WATCH_FROM * seconds
        self.want_hit = want_hit
        self.streams = []        # [{rid, cached_tokens, passes: [...]}]
        self.slot = None
        self._looked = 0.0

    def _pick(self, flight, blocks):
        """A slot at the first pass of a whole block, of a request nobody
        has recorded; a prefix-cache hit first, where one is wanted."""
        eng = self.engine
        seen = {s["rid"] for s in self.streams}
        holds = {}                       # slot -> the request it holds now
        for r in self.reqs:
            if r.slot is not None and r.t_admit is not None and (
                    r.slot not in holds
                    or r.t_admit > holds[r.slot].t_admit):
                holds[r.slot] = r
        first = flight.mask & (blocks < 0).all(axis=1) \
            & (eng.block_passes == 1)
        hit_needed = self.want_hit and not any(
            s["cached_tokens"] for s in self.streams)
        for slot in np.flatnonzero(first):
            req = holds.get(int(slot))
            cached = eng.last_admit_info(int(slot)).get("cached_tokens", 0)
            if req is None or req.rid in seen or not req.out_tokens \
                    or (hit_needed and not cached):
                continue
            if req.max_new_tokens - len(req.out_tokens) \
                    < (N_BLOCKS + 1) * eng.block_length:
                continue            # its reply ends inside the sample
            self.streams.append({"rid": req.rid, "cached_tokens": cached,
                                 "prompt_tokens": len(req.prompt),
                                 "passes": []})
            return int(slot), req
        return None, None

    def __call__(self, flight, blocks):
        eng = self.engine
        now = time.perf_counter() - self.t0
        if self.slot is None:
            if len(self.streams) >= N_STREAMS or now < self.after \
                    or now - self._looked < 0.1:
                return
            self._looked = now
            self.slot, self.req = self._pick(flight, blocks)
            if self.slot is None:
                # (no hit in this batch: take any stream after a while)
                self.want_hit = self.want_hit and now < 2 * self.after
                return
        slot, req = self.slot, self.req
        if not flight.mask[slot]:
            self.streams.pop()               # released under the sample
            self.slot = None
            return
        start = int(flight.lengths[slot])
        seq = np.concatenate([req.prompt, np.asarray(req.out_tokens,
                                                     np.int32)])
        passes = self.streams[-1]["passes"]
        passes.append({
            "start": start, "seq": seq[:start].astype(np.int32),
            "seq_len": len(seq), "input": blocks[slot].copy(),
            "after": eng.block_tokens[slot].copy(),
            "passes_after": int(eng.block_passes[slot]),
            "logits": np.asarray(jax.device_get(
                take_logits(flight.logits, slot)), np.float32)})
        if len(passes) == self.n_passes:
            self.slot = None


def n_passes(cfg) -> int:
    """Passes a recorded stream holds: ``N_BLOCKS`` blocks of
    ``denoising_steps`` denoise passes and a commit."""
    return N_BLOCKS * (cfg.denoising_steps + 1)


def _reference(engine, sizes, width: int, B: int, **variant):
    """One compiled reference for token rows padded to ``width`` (block-
    causal: padding after the block changes nothing in or before it) and a
    block's ``B`` output positions."""
    fn = jax.jit(lambda p, t, out: reference.forward(
        p, t, sizes, out_positions=out, q_block=Q_BLOCK, **variant))

    def run(tokens, start):
        row = np.zeros(width, np.int32)
        row[:len(tokens)] = tokens
        lg, margin = fn(engine._params, jnp.asarray(row),
                        jnp.arange(start, start + B, dtype=jnp.int32))
        return np.asarray(lg), np.asarray(margin)
    return run


def pass_row(p, mask_id: int):
    """The token row a recorded pass stands for: the stream's tokens before
    the block, then the block's input (the mask token where undecided)."""
    return np.concatenate([p["seq"], np.where(p["input"] < 0, mask_id,
                                              p["input"])]).astype(np.int32)


def logit_rows(name, p, want, margin, ids: int):
    """[(what, |logit error| max over the vocabulary, routing margin)] a
    position of the block."""
    err = np.abs(p["logits"][:, :ids] - want[:, :ids]).max(axis=-1)
    return [(f"{name}.{j}", float(err[j]), float(margin[j]))
            for j in range(len(err))]


def logits_agree(rows) -> bool:
    errs = sorted(r[1] for r in rows)
    return bool(errs) and errs[len(errs) // 2] <= MEDIAN_ATOL \
        and sum(e > LOGIT_ATOL for e in errs) <= MAX_OVER \
        and errs[-1] <= FLIP_ATOL


def rule_holds(p, cfg) -> bool:
    """Rule 2 for one recorded pass: what the path unmasked is the rule
    applied to its own logits."""
    undecided = p["input"] < 0
    if not undecided.any():                  # the commit pass
        return p["passes_after"] == 0 and bool((p["after"] < 0).all())
    done = p["passes_after"] - 1
    x0, conf = reference.confidences(p["logits"])
    count = reference.pass_count(undecided.sum(), done, cfg.denoising_steps)
    took = undecided & (p["after"] >= 0)
    if (p["after"][~undecided] != p["input"][~undecided]).any() \
            or (p["after"][took] != x0[took]).any():
        return False
    want = reference.unmask_rule(undecided, conf, count, cfg.remasking,
                                 cfg.confidence_threshold)
    if (took == want).all():
        return True
    # a confidence within CONF_RTOL of the cut may fall either way
    cut = np.sort(np.where(undecided, conf, -np.inf))[-count]
    near = undecided & (np.abs(conf - cut) <= CONF_RTOL * cut)
    return bool((took == want)[~near].all()) and took.sum() == want.sum()


def run_shape(stream, cfg) -> bool:
    """A recorded stream: ``n_passes`` consecutive passes, the block's start
    moving by a block at every commit, which comes every ``denoising_steps
    + 1`` passes."""
    ps = stream["passes"]
    per = cfg.denoising_steps + 1
    return len(ps) == n_passes(cfg) and all(
        p["start"] == ps[0]["start"] + (i // per) * cfg.block_length
        and p["seq_len"] == p["start"]
        and (not (p["input"] < 0).any()) == (i % per == per - 1)
        for i, p in enumerate(ps))


def run(ctx):
    tr = ctx.traffic
    sizes = dict(ctx.config)
    cfg, engine = build_engine(ctx)
    ids = min(int(ctx.config["vocab_size"]), cfg.mask_token_id)
    B = cfg.block_length
    ctx.mark("weights_and_engine")
    warm_up(engine, ids, ctx.seed)
    ctx.mark("warm_up")
    compiles_warm = dict(ctx.compile_events)
    engine.reset_serving_stats()
    items = blockgen_traffic.requests(tr, ctx.seed, ctx.seconds, ids)
    ctx.say(phase="traffic", **traffic_lib.length_summary(items),
            rate_rps=tr["rate_rps"], backlog=tr["backlog"],
            block_length=B, denoising_steps=cfg.denoising_steps,
            rule=cfg.remasking)

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
    reclaimed0 = engine.allocator.reclaimed
    setup_s = time.perf_counter() - ctx.t0
    if tracer:
        tracer.start()
    # ``serve_runner.measure`` with the recorder between the requests it
    # builds and the serve
    reqs = serve_runner._requests(items)
    recorder = engine.on_block_pass = Recorder(
        engine, reqs, ctx.seconds, want_hit=tr["shared_prefix"]["share"] > 0)
    live, done = [], threading.Event()

    def sample():
        while not done.wait(1.0):
            live.append(engine.allocator.blocks_in_use())
    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()
    t = time.perf_counter()
    report = engine.serve(reqs, temperature=0.0, max_wall_s=ctx.seconds)
    wall = time.perf_counter() - t
    done.set()
    sampler.join()
    engine.on_block_pass = None
    if tracer:
        tracer.join()
    compiles_window = int(ctx.compile_events.get("n", 0))

    s = serve_runner.summarize(reqs, wall)
    snapshot = {k: report.get(k) for k in (
        "iterations", "completed", "occupancy_mean", "decode_tokens",
        "prefill_tokens", "decode_step_ms", "queue_wait_ms", "prefix",
        "admission", "wall_s", "model_counters", "block_gap_ms",
        "lookahead_share", "cache_classes")}
    half = live[len(live) // 2:]
    kv = {"num_blocks": engine.num_blocks,
          "block_bytes": engine.cache_spec.block_nbytes(),
          "live_blocks_mean": float(np.mean(half)) if half else None,
          "live_blocks_max": max(live, default=None),
          "live_blocks_by_second": live,
          "reclaimed_in_window": engine.allocator.reclaimed - reclaimed0}
    peak_window = _common.memory_peak_bytes(ctx.devices)

    # The reference, after the window, from the streams' tokens alone.
    streams = [st for st in recorder.streams if st["passes"]]
    longest = max([p["start"] for st in streams for p in st["passes"]],
                  default=0) + B
    width = -(-max(longest, Q_BLOCK) // Q_BLOCK) * Q_BLOCK
    # (``reference``: overrides a REHEARSAL's configuration file hands the
    # reference alone, to show that the comparison can fail)
    ref = _reference(engine, sizes, width, B,
                     **(ctx.config.get("reference") or {}))
    rows, rules, shapes = [], [], []
    for i, st in enumerate(streams):
        shapes.append(run_shape(st, cfg))
        for j, p in enumerate(st["passes"]):
            want, margin = ref(pass_row(p, cfg.mask_token_id), p["start"])
            rows += logit_rows(f"s{i}.p{j}", p, want, margin, ids)
            rules.append(rule_holds(p, cfg))
    controls = {}
    for name, variant in CONTROLS.items():
        low = _reference(engine, sizes, width, B, **variant)
        controls[name] = []
        for j, p in enumerate(streams[0]["passes"] if streams else []):
            want, margin = low(pass_row(p, cfg.mask_token_id), p["start"])
            controls[name] += logit_rows(f"s0.p{j}", p, want, margin, ids)
    controls_fail = {name: not logits_agree(c)
                     for name, c in controls.items()}
    agree = {"logits": logits_agree(rows),
             "rule": bool(rules) and all(rules),
             "passes": len(streams) == N_STREAMS and all(shapes)}
    errs = sorted(r[1] for r in rows)
    correct = s["failed"] == 0 and all(agree.values()) \
        and all(controls_fail.values()) and compiles_window == 0 \
        and s["output_tokens"] > 0
    ctx.say(phase="serve", model=cfg.name, setup_s=setup_s, wall_s=wall,
            setup_marks_s=ctx.marks, compiles_warm_up=compiles_warm,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            logit_checks=rows, agree=agree,
            summary_checks={
                "positions": len(errs),
                "logit_median": errs[len(errs) // 2] if errs else None,
                "logit_max": errs[-1] if errs else None,
                "over_atol": sum(e > LOGIT_ATOL for e in errs)},
            recorded=[{"rid": st["rid"], "cached_tokens": st["cached_tokens"],
                       "prompt_tokens": st["prompt_tokens"],
                       "starts": [p["start"] for p in st["passes"]]}
                      for st in streams],
            control_checks={n: sorted(r[1] for r in c)
                            for n, c in controls.items()},
            controls_fail=controls_fail,
            limits={"median": MEDIAN_ATOL, "position": LOGIT_ATOL,
                    "max_over": MAX_OVER, "flipped": FLIP_ATOL,
                    "conf_rtol": CONF_RTOL},
            paged_kernel=engine.paged_kernel, max_slots=engine.max_slots,
            prefill_chunk=engine.prefill_chunk, kv=kv,
            memory_peak_bytes_at_window_end=peak_window,
            offered_tokens_per_s=sum(r.max_new_tokens for r in reqs)
            / ctx.seconds, snapshot=snapshot, **s)

    record = {
        "kind": "serve", "correct": correct, "attempted": s["attempted"],
        "failed": s["failed"],
        "end_to_end": {"serve_tokens_per_s": s["tokens_per_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "summary": s, "snapshot": snapshot, "kv": kv,
        "sdar": {k: ctx.config[k] for k in SDAR_KEYS if k in ctx.config},
        "blockgen": {"block_length": B,
                     "denoising_steps": cfg.denoising_steps,
                     "rule": cfg.remasking},
        "chips": len(ctx.devices), "peaks": ctx.peaks,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "serve", len(ctx.devices),
            cpu_rehearsal=ctx.rehearsal) if ctx.trace else None,
    }
    engine.close()
    return record

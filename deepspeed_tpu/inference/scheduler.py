"""Continuous batching: iteration-level scheduling over the slot cache.

The Orca insight, host-side: the scheduler's unit of work is one decode
ITERATION, not one request. Every iteration it (1) admits arrived
requests into free slots (prefill runs as its own compiled program —
prefill/decode disaggregation — and splices straight into the slot),
(2) runs ONE decode step for every live slot, and (3) evicts the slots
that finished. Requests join and leave mid-flight; the compiled decode
program never notices, because admission and eviction are counter
updates plus a dynamic_update_slice splice (inference/kv_cache.py).

The plain decode loop runs ONE ITERATION AHEAD of its token fetch: a
pass dispatches iteration n+1 — who is in it follows from the lengths
of the replies, not from n's tokens, and the program reads n's tokens
where they lie on the device — and only then fetches, emits and
accounts for iteration n, under n+1's device time (dispatch(n+1) ->
fetch(n) -> emit(n); ``InferenceEngine.decode_once`` with
``continuing``). A stream that stops on an EOS is found out one
iteration late: the row computed for it meanwhile is dropped and
counted, and the user sees the same tokens. An admission's programs
queue on the device behind the iteration in flight. The speculative
loop stays synchronous (the accepted count decides the lengths).

The arrival process is OPEN-LOOP: requests carry absolute arrival
offsets and join the queue when the wall clock passes them, whether or
not the engine has capacity — so TTFT honestly includes queue wait, and
offered load above capacity shows up as a growing queue, not as a
throttled arrival rate (the closed-loop benchmarking mistake).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..monitor.telemetry import ids_arg, spans_recorded
from ..utils.logging import logger
from .kv_cache import PromptChain


@dataclasses.dataclass
class Request:
    """One generation request in the open-loop stream."""
    rid: int
    prompt: np.ndarray                  # [P] int32 token ids
    max_new_tokens: int = 16
    arrival_s: float = 0.0              # offset from serve() start
    # -- runtime state (scheduler-owned) --
    slot: Optional[int] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    t_arrival: float = 0.0              # absolute clock
    t_admit: Optional[float] = None     # slot acquired (queue wait end)
    t_first: Optional[float] = None     # first token produced (TTFT end)
    t_last: Optional[float] = None      # latest token produced
    admission_attempts: int = 0         # head-of-queue rejections
    flying: int = 0                     # tokens dispatched, not yet fetched
    # A model of blocks hands a stream its tokens a block at a time: the
    # clock at each block's commit and the tokens it brought.
    block_times: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list, repr=False, compare=False)
    # Rows of the serving timeline (monitor/serving.py) this request was
    # live in: every live stream emits in every row between them
    # (``row_last`` stays -1 while it is).
    row_first: int = -1
    row_last: int = -1
    timeline: Any = dataclasses.field(default=None, repr=False,
                                      compare=False)
    # The prompt with its chain of block hashes (``chained``): walked once,
    # however many passes ask the engine whether the request can go in.
    chain: Optional[PromptChain] = dataclasses.field(
        default=None, repr=False, compare=False)

    def chained(self) -> PromptChain:
        """The prompt as the engine's admission calls take it."""
        if self.chain is None:
            self.chain = PromptChain(self.prompt)
        return self.chain

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None \
            else self.t_first - self.t_arrival

    @property
    def queue_wait_s(self) -> Optional[float]:
        """Arrival → admission: the router/scheduler backlog share of
        TTFT (the part more replicas would fix)."""
        return None if self.t_admit is None \
            else self.t_admit - self.t_arrival

    @property
    def service_ttft_s(self) -> Optional[float]:
        """Admission → first token: the prefill share of TTFT (the part
        a faster prefill would fix)."""
        if self.t_admit is None or self.t_first is None:
            return None
        return self.t_first - self.t_admit

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time per output token AFTER the first (the streaming
        cadence a user sees); None for single-token responses."""
        if self.t_first is None or self.t_last is None \
                or len(self.out_tokens) < 2:
            return None
        return (self.t_last - self.t_first) / (len(self.out_tokens) - 1)

    def token_times(self) -> np.ndarray:
        """The host clock at each delivery of tokens to this request:
        ``t_first`` (out of its prefill), then the emission of every
        row it was live in (one token a row; under speculative decoding
        the 1..k+1 tokens of a row arrive together).  Rows the ring no
        longer holds are left out.  A model of blocks: every token at its
        block's time (the gap between tokens is 0 inside a block)."""
        if self.block_times:
            times, counts = zip(*self.block_times)
            return np.repeat(np.asarray(times, float), counts)
        if self.t_first is None:
            return np.zeros(0)
        if self.row_first < 0:
            return np.array([self.t_first])
        return np.concatenate([[self.t_first], self.timeline.t_emit(
            self.row_first, self.row_last)])


def synthetic_requests(n: int, prompt_len: Tuple[int, int] = (8, 16),
                       max_new_tokens: int = 16, rate_rps: float = 0.0,
                       vocab_size: int = 512, seed: int = 0
                       ) -> List[Request]:
    """An open-loop synthetic arrival stream: ``rate_rps`` > 0 draws
    exponential inter-arrival gaps (Poisson arrivals at that rate);
    rate 0 = everything arrives at t=0 (the saturation stream the
    occupancy acceptance gate uses). Prompts are uniform random tokens
    with lengths in ``prompt_len`` (inclusive)."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    lo, hi = prompt_len
    for i in range(n):
        if rate_rps > 0 and i > 0:
            t += float(rng.exponential(1.0 / rate_rps))
        plen = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(0, vocab_size, size=plen).astype(np.int32)
        out.append(Request(rid=i, prompt=prompt,
                           max_new_tokens=max_new_tokens, arrival_s=t))
    return out


def shared_prefix_requests(n: int, prefix_len: int = 32,
                           tail_len: Tuple[int, int] = (4, 12),
                           max_new_tokens: int = 16,
                           rate_rps: float = 0.0, vocab_size: int = 512,
                           seed: int = 0) -> List[Request]:
    """The shared-prefix open-loop workload: every request carries the
    SAME ``prefix_len``-token system prompt followed by a random tail
    in ``tail_len`` (inclusive) — the traffic shape prefix-shared
    paging is built for (common system prompts / few-shot preambles,
    varying user turns). Arrival process as in
    ``synthetic_requests``."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab_size, size=prefix_len).astype(np.int32)
    t = 0.0
    out = []
    lo, hi = tail_len
    for i in range(n):
        if rate_rps > 0 and i > 0:
            t += float(rng.exponential(1.0 / rate_rps))
        tail = rng.integers(0, vocab_size,
                            size=int(rng.integers(lo, hi + 1))
                            ).astype(np.int32)
        out.append(Request(rid=i,
                           prompt=np.concatenate([prefix, tail]),
                           max_new_tokens=max_new_tokens, arrival_s=t))
    return out


class ContinuousBatchingScheduler:
    """Per-iteration insert/evict over an InferenceEngine's slots."""

    def __init__(self, engine, temperature: float = 0.0,
                 eos_token: Optional[int] = None,
                 idle_sleep_s: float = 0.0005,
                 max_wall_s: Optional[float] = None,
                 trace=None):
        self.engine = engine
        self.temperature = float(temperature)
        self.eos_token = eos_token
        self.idle_sleep_s = float(idle_sleep_s)
        self.max_wall_s = max_wall_s
        # Request-scoped span recorder (monitor/request_trace.py): the
        # router passes a shared one so a request's route decision and
        # its replica-side spans land in the same record; standalone
        # serves build their own when telemetry is on. Pure host state —
        # zero added device syncs either way.
        if trace is None and getattr(engine.telemetry, "enabled", False):
            from ..monitor.request_trace import RequestTrace
            trace = RequestTrace()
        self.trace = trace
        # Rows a stream's step writes: a model of blocks' block, else one.
        self._step_rows = getattr(engine, "block_length", 0) or 1

    # ------------------------------------------------------------------ #
    def _finished(self, req: Request, slot_len: int) -> bool:
        if len(req.out_tokens) >= req.max_new_tokens:
            return True
        if self.eos_token is not None and req.out_tokens and \
                req.out_tokens[-1] == self.eos_token:
            return True
        # Slot full: the next step would have nowhere to write.
        return slot_len + self._step_rows > self.engine.max_len

    def _continues(self, req: Request, slot: int) -> bool:
        """Whether ``req`` takes part in the iteration dispatched next:
        not when the tokens it has in flight complete its reply or fill
        its slot (the engine's lengths move at the dispatch, so they
        hold those already). An EOS among them cannot be known yet."""
        return len(req.out_tokens) + req.flying < req.max_new_tokens \
            and self.engine.context_len(slot) + self._step_rows \
            <= self.engine.max_len

    def _leave_rows(self, req: Request) -> None:
        """The latest row is the last this request emitted in."""
        if req.row_first >= 0:
            req.row_last = self.engine.serving.rows - 1

    def _complete(self, req: Request) -> None:
        self._leave_rows(req)
        self.engine.complete_request(
            req.rid, req.ttft_s or 0.0, req.tpot_s,
            prompt_tokens=len(req.prompt),
            new_tokens=len(req.out_tokens),
            queue_wait_s=req.queue_wait_s,
            service_ttft_s=req.service_ttft_s,
            admission_attempts=req.admission_attempts)
        if self.trace is not None:
            self.trace.complete(req.rid, t=req.t_last,
                                telemetry=self.engine.telemetry)

    def _reject(self, req: Request, queue_len: int) -> str:
        """Head-of-queue admission rejection: per-request attempt count,
        aggregator total, first-rejection event, trace mark. Returns the
        reason (the ``admit`` span's ``rejected``)."""
        eng = self.engine
        req.admission_attempts += 1
        reason = getattr(eng, "last_admit_block", None) or "no_slot"
        if self.trace is not None:
            self.trace.admit_reject(req.rid, reason=reason)
        note = getattr(eng, "note_admission_reject", None)
        if note is not None:
            note(req.rid, reason, req.admission_attempts, queue_len)
        return reason

    def _activate(self, req: Request, slot: int, tok: int, t_first: float,
                  active: Dict[int, Request]) -> None:
        """A prefilled request takes its slot (or is done with its first
        token): from here it emits in every row of the timeline."""
        eng = self.engine
        req.slot = slot
        if self._step_rows > 1:
            # A model of blocks: the prefill yields no token; the first
            # arrive with the first block's commit (``_emit``).
            req.out_tokens = []
            eng.activate_block(slot, req.prompt)
            eng.serving.note_prefill(len(req.prompt))
            self._admit_trace(req, slot, t_first)
            if self._finished(req, eng.context_len(slot)):
                self._complete(req)
                eng.release_slot(slot)
            else:
                active[slot] = req
            return
        req.t_first = req.t_last = t_first
        req.out_tokens = [tok]
        eng.activate_slot(slot, len(req.prompt), tok)
        eng.serving.note_prefill(len(req.prompt))
        self._admit_trace(req, slot)
        if self._finished(req, eng.context_len(slot)):
            self._complete(req)
            eng.release_slot(slot)
        else:
            # Its first row is the next to be written, or the one after
            # where that is an iteration dispatched without it.
            later = int(any(r.flying for r in active.values()))
            req.timeline = eng.serving
            req.row_first = eng.serving.rows + later
            eng.serving.note_first_token(req.t_first, later)
            active[slot] = req

    def _admit_trace(self, req: Request, slot: int,
                     t_prefilled: Optional[float] = None) -> None:
        """The request's admission and prefill on its trace, and its first
        token where the prefill yields it (``t_prefilled``: the prefill's
        end for a model of blocks, whose first tokens come later)."""
        if self.trace is None:
            return
        eng = self.engine
        self.trace.admit(req.rid, slot, t=req.t_admit,
                         replica=getattr(eng, "replica", "") or None)
        info_fn = getattr(eng, "last_admit_info", None)
        info = info_fn(slot) if info_fn is not None else {}
        self.trace.prefill(req.rid, (t_prefilled or req.t_first
                                     or req.t_admit)
                           - req.t_admit, tokens=len(req.prompt),
                           chunks=info.get("chunks", 1),
                           cached_tokens=info.get("cached_tokens", 0),
                           cow_fork=info.get("cow_fork", False))
        if t_prefilled is None:
            self.trace.first_token(req.rid, t=req.t_first)

    def _emit(self, sampled: np.ndarray, took: np.ndarray,
              active: Dict[int, Request]) -> None:
        """Hand an iteration's tokens to the streams that were in it
        (``took``; a fetched iteration is a row of the timeline even
        where every stream it held has ended since) and evict the ones
        that finished.  A model of blocks: ``sampled`` is ``[S, B]`` and
        ``took`` the tokens each slot is handed, the LAST ``took[slot]``
        of its row (0 for a stream whose pass committed nothing: it is
        not in this row of the timeline); a stream's first tokens are its
        first block's, and the gap between its blocks goes to the
        aggregator's block-gap histogram."""
        eng, tel, agg, trace = (self.engine, self.engine.telemetry,
                                self.engine.serving, self.trace)
        blocks = self._step_rows > 1
        with tel.span("emit") as span:
            slots = [slot for slot in active if took[slot]]
            occ = len(slots)
            t_now, row = agg.note_emit(occ)
            finished, gaps = [], []
            for slot in slots:
                req = active[slot]
                n = int(took[slot])
                req.flying -= n
                if blocks:
                    self._take_block(req, sampled[slot, -n:], t_now, gaps)
                else:
                    req.out_tokens.append(int(sampled[slot]))
                req.t_last = t_now
                if trace is not None:
                    trace.tick(req.rid, occ, n, t=t_now, row=row)
                # The engine's length holds the rows in flight too (a
                # block's whole, where its commit is in flight).
                flying = self._step_rows if blocks and req.flying \
                    else req.flying
                if self._finished(req, eng.context_len(slot) - flying):
                    self._complete(req)
                    eng.release_slot(slot)
                    del active[slot]
                    finished.append(req.rid)
            if blocks:
                agg.note_block_gaps(gaps)
            if spans_recorded(tel):
                span.set_metadata(finished=ids_arg(finished),
                                  **agg.emit_args(occ))
                if blocks:
                    span.set_metadata(blocks=occ, block_gaps_ms=ids_arg(
                        round(g * 1e3, 3) for g in gaps))
        agg.lap("emit_s")

    def _take_block(self, req: Request, tokens, t_now: float,
                    gaps: List[float]) -> None:
        """A committed block's reply tokens reach ``req`` (no further than
        its budget or an EOS among them); the time since its block before
        goes into ``gaps``."""
        toks = [int(t) for t in tokens]
        if self.eos_token is not None and self.eos_token in toks:
            toks = toks[:toks.index(self.eos_token) + 1]
        toks = toks[:max(req.max_new_tokens - len(req.out_tokens), 0)]
        if req.t_first is None:
            req.t_first = t_now
            if self.trace is not None:
                self.trace.first_token(req.rid, t=t_now)
        else:
            gaps.append(t_now - req.t_last)
        req.out_tokens.extend(toks)
        req.block_times.append((t_now, len(toks)))

    # ------------------------------------------------------------------ #
    def serve(self, requests: Sequence[Request]) -> Dict[str, Any]:
        """Run the stream to completion; returns the serving report
        (the aggregator snapshot + per-request records)."""
        eng = self.engine
        tel = eng.telemetry
        agg = eng.serving
        clock = agg.clock
        t0 = agg.note_serve_start()
        trace = self.trace
        pending = deque(sorted(requests, key=lambda r: r.arrival_s))
        queue: deque = deque()
        active: Dict[int, Request] = {}
        # Speculative decoding emits 1..k+1 tokens per slot per
        # iteration; greedy only — exact rejection sampling for
        # temperature > 0 is not implemented, so sampling streams fall
        # back to plain decode.
        spec = bool(getattr(eng, "spec_enabled", False)) and \
            self.temperature == 0.0

        try:
            self._loop(pending, queue, active, t0, spec)
        except BaseException:
            # Nothing stays in flight and no slot stays held.
            self._discard_in_flight()
            for slot in list(active):
                eng.release_slot(slot)
            raise

        wall = clock() - t0
        # Final drain with a SERVE-WALL-anchored snapshot: a run shorter
        # than report_steps iterations would otherwise never put the
        # aggregator snapshot (tokens/s, decode-step percentiles) into
        # any report record, and telemetry_report's serving section
        # would carry nulls; the last report record wins there, so this
        # also pins the figure benches compare to the same wall
        # SERVE_BENCH.json uses.
        if tel.enabled:
            tel.drain({"serving": eng.serving.snapshot(
                wall_s=wall)})
        report = dict(eng.serving.snapshot(wall_s=wall))
        if report.get("stalls"):
            # An untraced run that lost seconds says where its thread
            # stood (docs/tutorials/inference.md).
            logger.warning("serve: %d stalled interval(s): %s", len(
                report["stalls"]), "; ".join(
                    f"row {st['row']} at {st['at_s']} s waited "
                    f"{st['gap_ms']} ms, {st['in_ms']} ms over the usual "
                    f"in {st['in']}" for st in report["stalls"]))
        report["recompiles"] = eng.telemetry.recompile_count
        report["unfinished"] = len(pending) + len(queue) + len(active)
        if trace is not None:
            report["trace"] = trace.summary()
        report["requests"] = [
            {"rid": r.rid, "prompt_tokens": len(r.prompt),
             "new_tokens": len(r.out_tokens),
             "ttft_ms": round(r.ttft_s * 1e3, 3)
             if r.ttft_s is not None else None,
             "tpot_ms": round(r.tpot_s * 1e3, 3)
             if r.tpot_s is not None else None,
             "tokens": list(map(int, r.out_tokens))}
            for r in sorted(requests, key=lambda r: r.rid)]
        return report

    def _discard_in_flight(self) -> None:
        """A serve that ends early forgets the iteration in flight (its
        tokens would be emitted after the end); the caller releases the
        slots."""
        discard = getattr(self.engine, "decode_discard", None)
        if discard is not None:
            discard()

    def _loop(self, pending: deque, queue: deque,
              active: Dict[int, Request], t0: float, spec: bool) -> None:
        """``serve``'s passes, until the stream is through or
        ``max_wall_s`` cuts it."""
        eng = self.engine
        tel = eng.telemetry
        agg = eng.serving
        clock = agg.clock
        trace = self.trace
        ledger = getattr(agg, "ledger", None)
        while pending or queue or active:
            now = clock() - t0
            if self.max_wall_s is not None and now > self.max_wall_s:
                # Abandon the run WITHOUT leaking capacity: mid-flight
                # slots must come back, or the engine's next serve()
                # starts with no free slots and spins forever.
                self._discard_in_flight()
                abort = getattr(eng, "abort_request", None)
                t_ab = clock()
                for slot in list(active):
                    req = active[slot]
                    self._leave_rows(req)
                    if trace is not None:
                        trace.abort(req.rid, "max_wall", t=t_ab,
                                    telemetry=eng.telemetry)
                    if abort is not None:
                        abort(req.rid, "max_wall")
                    eng.release_slot(slot)
                    del active[slot]
                for req in queue:
                    # Enqueued but never admitted: starved, not served —
                    # counts against SLO availability like any abort.
                    if trace is not None:
                        trace.abort(req.rid, "starved", t=t_ab,
                                    telemetry=eng.telemetry)
                    if abort is not None:
                        abort(req.rid, "starved")
                break
            # 1. open-loop arrivals join the queue on schedule. How late
            # this pass polled for the oldest of them rides on its
            # ``admit`` spans (``late_ms``: the generator's lateness).
            late_ms = max(0.0, now - pending[0].arrival_s) * 1e3 \
                if pending else 0.0
            while pending and pending[0].arrival_s <= now:
                req = pending.popleft()
                req.t_arrival = t0 + req.arrival_s
                if trace is not None:
                    trace.enqueue(req.rid, t=req.t_arrival)
                queue.append(req)
            # 2. admissions: prefill into free slots. FCFS — when the
            # head of the queue cannot be admitted (no slot, or the
            # block pool cannot cover its worst case), everything
            # behind it waits; pool exhaustion rejects admission here
            # and NEVER touches a live slot. Admission is in
            # one-slot-per-group BATCHES (engine.prefill_many): a full
            # batch prefills G admissions for one admission's wall.
            admitting = bool(queue)
            if admitting:
                agg.lap("other_s")
            while queue:
                with tel.span("admit", queued=len(queue),
                              late_ms=late_ms) as span:
                    batch = []
                    used: set = set()
                    rejected = ""
                    while queue:
                        req = queue[0]
                        slot = eng.select_slot(
                            req.chained(), req.max_new_tokens,
                            exclude_groups=used)
                        if slot is None:
                            # Only a rejection with NO exclusions is the
                            # gate refusing the head (with exclusions it
                            # may just be this batch's one-slot-per-group
                            # shape).
                            if not used:
                                rejected = self._reject(req, len(queue))
                            break
                        queue.popleft()
                        req.t_admit = clock()
                        used.add(eng.group_of(slot))
                        batch.append((req, slot))
                    rids = [req.rid for req, _ in batch]
                    span.set_metadata(admitted=len(batch),
                                      rejected=rejected,
                                      rids=ids_arg(rids))
                if not batch:
                    break
                results = eng.prefill_many(
                    [(slot, req.chained(), req.max_new_tokens)
                     for req, slot in batch], self.temperature,
                    rids=rids)
                t_now = clock()
                for (req, slot), (tok, _) in zip(batch, results):
                    self._activate(req, slot, tok, t_now, active)
            if admitting:
                agg.lap("admit_s")
            # 3. one decode (or draft-then-verify) iteration for every
            # live slot.
            if active and spec:
                emitted, n_new = eng.spec_decode_once(self.temperature)
                with tel.span("emit") as span:
                    occ = len(active)
                    t_now, row = agg.note_emit(occ)
                    finished = []
                    for slot in list(active):
                        req = active[slot]
                        budget = req.max_new_tokens - len(req.out_tokens)
                        n = int(n_new[slot])
                        toks = [int(t) for t in emitted[slot, :n]]
                        if self.eos_token is not None and \
                                self.eos_token in toks:
                            toks = toks[:toks.index(self.eos_token) + 1]
                        req.out_tokens.extend(toks[:max(budget, 0)])
                        req.t_last = t_now
                        if trace is not None:
                            trace.tick(req.rid, occ, n, t=t_now,
                                       proposed=eng.spec_k,
                                       accepted=max(n - 1, 0), row=row)
                        if self._finished(req, eng.context_len(slot)):
                            self._complete(req)
                            eng.release_slot(slot)
                            del active[slot]
                            finished.append(req.rid)
                    if spans_recorded(tel):
                        span.set_metadata(finished=ids_arg(finished),
                                          **agg.emit_args(occ))
                agg.lap("emit_s")
            elif active:
                # One pass of the loop that runs an iteration ahead:
                # dispatch the next iteration for every stream whose
                # reply the tokens in flight do not complete, THEN fetch
                # and hand out the tokens of the iteration in flight.
                if self._step_rows > 1 and not eng.served.runs_ahead:
                    # Blocks under the dynamic rule: a commit is the
                    # device's news, so every pass is fetched before the
                    # next is dispatched.
                    sampled, _ = eng.decode_once(self.temperature)
                    for slot, req in active.items():
                        req.flying += int(eng.last_yield[slot])
                    self._emit(sampled, eng.last_yield, active)
                    continue
                going = [slot for slot, req in active.items()
                         if self._continues(req, slot)]
                sampled, took = eng.decode_once(self.temperature,
                                                continuing=going)
                # (what the dispatch hands each slot at its fetch: a token,
                # or what a model of blocks' pass commits)
                yields = getattr(eng, "dispatch_yield", None)
                for slot in going:
                    active[slot].flying += 1 if yields is None \
                        else int(yields[slot])
                if took is not None:
                    self._emit(sampled, took, active)
            elif pending and not queue:
                # Idle ahead of the next arrival — open-loop wait. The
                # watchdog heartbeat says "idle, not hung": a sparse
                # arrival stream must not read as a decode-loop stall.
                tel.heartbeat()
                gap = pending[0].arrival_s - (clock() - t0)
                if gap > 0:
                    with tel.span("serve_idle", why="no_arrival"):
                        t_sl = clock()
                        time.sleep(min(gap, self.idle_sleep_s))
                        if ledger is not None:
                            ledger.note("idle",
                                        clock() - t_sl)
            elif queue:
                # Queued work but no free slot and nothing decoding:
                # capacity is held outside this serve (caller-activated
                # slots). Yield instead of busy-spinning — unless
                # nothing can EVER free the capacity the head request
                # needs (an over-sized request on an idle engine), which
                # must fail loudly, not hang.
                if not active and not pending and not eng.active.any():
                    req = queue[0]
                    raise RuntimeError(
                        f"request {req.rid} can never be admitted: "
                        f"{len(req.prompt)} prompt + "
                        f"{req.max_new_tokens} new tokens exceeds the "
                        "block pool's per-group capacity")
                tel.heartbeat()
                with tel.span("serve_idle", why="admission_blocked"):
                    t_sl = clock()
                    time.sleep(self.idle_sleep_s)
                    if ledger is not None:
                        ledger.note("admission_blocked",
                                    clock() - t_sl)

__all__ = ["Request", "synthetic_requests", "shared_prefix_requests",
           "ContinuousBatchingScheduler"]

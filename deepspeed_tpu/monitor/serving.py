"""Per-request goodput metrics for the serving tier.

The serving analogues of the training spine's step records: while the
trainer's unit of accounting is the optimizer step, serving accounts per
REQUEST (TTFT — time to first token, queue wait included; TPOT — mean
time per output token after the first) and per decode ITERATION (batch
occupancy = active slots / total slots; the number that says whether
continuous batching is actually keeping the chip busy).

All inputs are host wall-clock and host counters — aggregation adds
zero device syncs. ``ServingAggregator.snapshot()`` is the one shape
every consumer speaks: the engine's drain extra, SERVE_BENCH.json, and
``tools/telemetry_report.py``'s ``serving`` section.

The per-iteration numbers are ROWS of one timeline (``COLUMNS``): the
loop hands the clock to ``lap`` at the boundaries its spans already
have, so the time between two token emissions is split, without a
remainder, into the columns of the row the later emission closes.  A
stream's inter-token intervals, what filled them and the run's worst
stalls are read from the rows (``snapshot()``: ``itl_ms``,
``itl_split_ms``, ``stalls``); a request finds its own token times by
the rows it was live in (``Request.token_times``).
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

# One row per decode iteration (= one scheduler pass that emits tokens).
# Seconds, as the host clock read them, between the emission before and
# this one: ``emit_s`` (the emit loop of the pass before), ``other_s``
# (between spans: arrivals, the loop itself, an idle wait), ``admit_s``,
# ``prefill_s`` + ``copy_s`` (OTHER requests' admissions: every live
# stream stands still), and the ``decode`` span's four parts; their sum
# is ``gap_s``.  Counts of the same interval: ``admitted`` streams (their
# own first intervals, from their first tokens, sum to ``first_gap_s``,
# of which ``first_stall_s`` in later admissions), prefill dispatches,
# the rows they needed (prompt - cached) and the rows they computed.
# ``continuing`` streams waited the whole ``gap_s`` (none where no
# scheduler handed the tokens out: nobody said who waited).  The rest is
# the iteration's own sample: ``ahead`` is 1 where it was dispatched
# while the iteration before was still unfetched (the loop runs one
# iteration ahead of its token fetch: between two emissions lie the
# dispatch of the NEXT iteration and the fetch of this one), ``dropped``
# the rows it computed for streams that had ended by then.
COLUMNS = ("t_emit", "gap_s", "emit_s", "other_s", "admit_s", "prefill_s",
           "copy_s", "tables_s", "dispatch_s", "fetch_s", "advance_s",
           "first_gap_s", "first_stall_s", "continuing", "admitted",
           "prefill_dispatches", "prefill_rows", "prefill_rows_computed",
           "occupancy", "decode_ms", "cache_bytes", "context_tokens",
           "ahead", "dropped")
COL = {name: i for i, name in enumerate(COLUMNS)}
# The columns a gap is made of, under the names an operator knows them
# by (the host spans'; ``other_s`` is no span's).
GAP_PARTS = {"emit_s": "emit", "other_s": "between_spans",
             "admit_s": "admit", "prefill_s": "prefill", "copy_s": "copy",
             "tables_s": "decode_tables", "dispatch_s": "decode_dispatch",
             "fetch_s": "decode_fetch", "advance_s": "decode_advance"}
RING = 65536                 # rows kept: a 51 s window of the fastest
#                              benchmark cell is ~6,000
# The stall rule, one for the serving and the training timeline
# (``stall_rows``): an interval over STALL_TIMES_MEDIAN medians and over
# STALL_FLOOR_S; the STALLS_KEPT longest are kept.
STALL_FLOOR_S = 0.25
STALL_TIMES_MEDIAN = 10.0
STALLS_KEPT = 8


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted list (the same rule
    tools/telemetry_report.py uses — keep the figures comparable)."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return float(sorted_vals[k])


def _pcts(vals: List[float]) -> Dict[str, float]:
    s = sorted(vals)
    return {"p50": round(percentile(s, 50), 3),
            "p95": round(percentile(s, 95), 3),
            "mean": round(sum(s) / len(s), 3) if s else 0.0,
            "n": len(s)}


def weighted_percentile(values: np.ndarray, weights: np.ndarray,
                        q: float) -> float:
    """``percentile``'s nearest-rank rule over ``values`` repeated
    ``weights`` times each (whole, non-negative counts)."""
    order = np.argsort(values, kind="stable")
    cum = np.cumsum(weights[order])
    if not len(cum) or cum[-1] <= 0:
        return 0.0
    k = int(round(q / 100.0 * (cum[-1] - 1)))
    return float(values[order][np.searchsorted(cum, k, side="right")])


def stall_limit(gap_s: np.ndarray) -> float:
    """The seconds an interval must exceed to be a stall where the usual
    ones are ``gap_s``: the larger of ``STALL_TIMES_MEDIAN`` x their
    median and ``STALL_FLOOR_S``."""
    return max(STALL_TIMES_MEDIAN * float(np.median(gap_s)), STALL_FLOOR_S) \
        if len(gap_s) else STALL_FLOOR_S


def stall_rows(gap_s: np.ndarray, usual=None) -> np.ndarray:
    """Which of the intervals ``gap_s`` (seconds, in order of time) are
    kept as stalls: those over ``stall_limit`` of the ``usual`` ones (a
    mask; all of them by default), the ``STALLS_KEPT`` longest of them,
    as indices in order of time."""
    limit = stall_limit(gap_s if usual is None else gap_s[usual])
    worst = np.flatnonzero(gap_s > limit)
    return np.sort(worst[np.argsort(-gap_s[worst])][:STALLS_KEPT])


class ServingAggregator:
    """Accumulates per-iteration and per-request serving metrics.

    ``label`` names the replica this aggregator feeds (the multi-
    replica router runs one engine — and one aggregator — per replica);
    snapshots carry it so downstream consumers (telemetry_report,
    SERVE_BENCH.json) never interleave two replicas' percentile streams
    into one misleading distribution. ``ServingAggregator.merged``
    builds the honest aggregate view by POOLING the raw samples.

    ``clock`` is the one clock of the serving loop: the scheduler and
    the engine read it through this object (``lap``), so a test that
    drives it drives every stamp.
    """

    def __init__(self, max_slots: int, label: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.max_slots = max(1, int(max_slots))
        self.label = label
        self.clock = clock
        self.t0 = clock()
        # The timeline: a ring of rows, ``rows`` of them written so far;
        # ``_pend`` gathers the row the next iteration closes.
        self._rows = np.zeros((RING, len(COLUMNS)))
        self._n = 0
        self._pend = [0.0] * len(COLUMNS)
        self._written = self._pend   # the latest row written, as a list
        self._last = self.t0         # the clock at the latest lap
        self._t_prev = self.t0       # t_emit of the row before
        # An iteration's row is written when its tokens are handed out
        # (``note_emit``); until then the clock when the iteration
        # ended, for a loop that has no scheduler to say so.
        self._staged: Optional[float] = None
        # First tokens since the last row: how many, the sum of their
        # times, and of the stall seconds the interval held before each;
        # ``_first_later`` the same for streams whose first row is the
        # one AFTER the next (an iteration without them is in flight).
        self._first = [0, 0.0, 0.0]
        self._first_later = [0, 0.0, 0.0]
        # Where the latest ``serve()`` began (``stalls`` are its own).
        self._serve_row0 = 0
        self._serve_t0 = self.t0
        self.iterations = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.prefill_width_dispatches = collections.Counter()  # by width
        self.completed = 0
        # Paged-cache accounting (engine-fed; stays empty — and out of
        # the snapshot — until the engine feeds it: the scheduler tests'
        # fake engines never do).
        self.prompt_tokens_admitted = 0
        self.cached_tokens_admitted = 0
        self.admissions = 0
        self.chain_walks = 0        # whole-prompt hash walks (the engine's)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._model_counters: Dict[str, Any] = {}   # name -> (sum, n)
        self._classes: Dict[str, Dict[str, int]] = {}   # cache classes'
        self._state: Dict[str, int] = {}    # a per-stream state pool's
        self._admit_classes: Dict[str, int] = {}    # admissions, by class
        # Analytic attend-work accounting (engine-fed): the same
        # iterations priced BOTH ways — the Pallas kernel's live-context
        # term vs the one-hot contraction's pool-capacity term. ``attend_mode`` names which one actually
        # ran; the totals are host arithmetic (projections), never
        # device measurements.
        self.attend_mode: Optional[str] = None
        self.attend_flops_kernel = 0
        self.attend_flops_onehot = 0
        self.attend_bytes_kernel = 0
        self.attend_bytes_onehot = 0
        self.attend_tokens = 0
        # The paged kernel's sequencing steps a layer, those of them that
        # touched a live block, and the live ones whose first copies
        # nothing had started (the ``decode`` span's counters).
        self.attend_steps = 0
        self.attend_live_steps = 0
        self.attend_cold_steps = 0
        # Admission-rejection accounting (the reservation gate's retries
        # used to be invisible): total rejected reservations plus the
        # per-completed-request attempt counts.
        self.reservations_rejected = 0
        self._admission_attempts: List[float] = []
        # Optional overlays (engine-attached): a ServingGoodputLedger
        # and an SLOTracker (monitor/serving_slo.py) — or, on a merged
        # aggregator, their already-settled snapshot dicts. When unset
        # the snapshot omits the sections (skip-never-fail downstream).
        self.ledger: Optional[Any] = None
        self.slo: Optional[Any] = None
        # A model generated in blocks: the seconds between a stream's
        # consecutive blocks (the gap between tokens is 0 inside one).
        self._block_gap_s: List[float] = []
        self._ttft_ms: List[float] = []
        self._tpot_ms: List[float] = []
        self._queue_wait_ms: List[float] = []
        self._service_ttft_ms: List[float] = []

    # ---- the timeline ---- #
    @property
    def rows(self) -> int:
        """Rows written so far (the index of the next)."""
        self._flush()
        return self._n

    def lap(self, column: str) -> float:
        """Read the clock and file the time since the last lap under
        ``column`` of the row being gathered; returns the reading."""
        if self._staged is not None:
            self._flush()
        now = self.clock()
        self._pend[COL[column]] += now - self._last
        self._last = now
        return now

    def note_serve_start(self) -> float:
        """A ``serve()`` begins: its ``stalls`` are counted from here."""
        self._serve_row0 = self.rows
        self._serve_t0 = self.clock()
        return self._serve_t0

    def note_prefill_pass(self, dispatches: int, rows: int,
                          rows_computed: int,
                          widths: Sequence[int] = ()) -> float:
        """One admission batch's prefill ends here (a lap of
        ``prefill_s``): the chunk programs it dispatched, the prompt
        rows it needed (prompt - cached), the rows those programs
        computed and each one's row width."""
        self.prefill_width_dispatches.update(int(w) for w in widths)
        p = self._pend
        p[COL["prefill_dispatches"]] += dispatches
        p[COL["prefill_rows"]] += rows
        p[COL["prefill_rows_computed"]] += rows_computed
        return self.lap("prefill_s")

    def note_first_token(self, t_first: float, rows_ahead: int = 0) -> None:
        """A stream joins the rows: its first interval runs from its
        first token (``t_first``, out of its prefill) to the next
        emission — to the one after with ``rows_ahead`` 1: the next row
        is an iteration dispatched without it — and the stall in it is
        only what later admissions add."""
        p, f = self._pend, self._first_later if rows_ahead else self._first
        f[0] += 1
        f[1] += t_first
        f[2] += p[COL["prefill_s"]] + p[COL["copy_s"]]

    def _write_row(self, t_emit: float, streams: int) -> None:
        """The gathered row, emitted at ``t_emit`` to ``streams`` (0: to
        nobody a scheduler knows of), goes into the ring."""
        p, (n, t_sum, stall_before) = self._pend, self._first
        p[COL["t_emit"]] = t_emit
        p[COL["gap_s"]] = t_emit - self._t_prev
        p[COL["continuing"]] = max(streams - n, 0) if self._n else 0
        p[COL["admitted"]] = n
        p[COL["first_gap_s"]] = n * t_emit - t_sum
        p[COL["first_stall_s"]] = \
            n * (p[COL["prefill_s"]] + p[COL["copy_s"]]) - stall_before
        self._rows[self._n % RING] = p
        self._n += 1
        self._t_prev = t_emit
        self._written = p            # the latest row, still a list
        self._pend = [0.0] * len(COLUMNS)
        # Streams due one row later: what this interval stalled them
        # after their first tokens goes with them.
        n, t_sum, stall_before = self._first_later
        self._first = [n, t_sum, stall_before - n * (
            p[COL["prefill_s"]] + p[COL["copy_s"]])]
        self._first_later = [0, 0.0, 0.0]

    def _flush(self) -> None:
        """Write an iteration's row that no scheduler emitted: it has
        its times and its sample, and no stream's interval."""
        if self._staged is not None:
            t_end, self._staged = self._staged, None
            self._write_row(t_end, 0)

    def note_emit(self, streams: int) -> "tuple[float, int]":
        """The scheduler hands this iteration's tokens to ``streams``
        requests NOW: the row's emission time (and with it the interval
        since the row before).  Returns the clock and the row's index."""
        self._staged = None
        now = self.lap("advance_s")
        self._write_row(now, streams)
        return now, self._n - 1

    def emit_args(self, streams: int) -> Dict[str, Any]:
        """The ``emit`` span's args of the row ``note_emit(streams)`` has
        just written (for a span that something records: the loop does
        not build them otherwise): ``row``, ``streams``, those of them
        that waited the whole interval, ``continuing``, and in ms this
        row's interval ``gap_ms`` with the part of it in other requests'
        prefill and copies, ``stall_ms``, and on the host outside the
        wait for the device, ``host_ms``."""
        p = self._written
        gap = p[COL["gap_s"]]
        stall = p[COL["prefill_s"]] + p[COL["copy_s"]]
        wait = p[COL["dispatch_s"]] + p[COL["fetch_s"]]
        return {"row": self._n - 1, "streams": int(streams),
                "continuing": int(p[COL["continuing"]]),
                "gap_ms": round(gap * 1e3, 4),
                "stall_ms": round(stall * 1e3, 4),
                "host_ms": round((gap - stall - wait) * 1e3, 4)}

    def t_emit(self, row_first: int, row_last: int = -1) -> np.ndarray:
        """Emission times of rows ``row_first..row_last`` (to the latest
        row for -1), those of them the ring still holds."""
        n = self.rows
        hi = n - 1 if row_last < 0 else min(row_last, n - 1)
        idx = np.arange(max(row_first, n - RING, 0), hi + 1)
        return self._rows[idx % RING, COL["t_emit"]]

    def _table(self) -> np.ndarray:
        """The rows held, oldest first."""
        n = self.rows
        if n <= RING:
            return self._rows[:n]
        return np.roll(self._rows, -(n % RING), axis=0)

    def _extend_rows(self, table: np.ndarray) -> None:
        """Append another aggregator's rows (``merged``)."""
        table = table[-RING:]
        self._rows[(self._n + np.arange(len(table))) % RING] = table
        self._n += len(table)

    # ---- per decode iteration ---- #
    def note_iteration(self, active_slots: int, decode_s: float,
                       cache_bytes: Optional[int] = None,
                       context_tokens: Optional[int] = None,
                       emitted_tokens: Optional[int] = None,
                       ahead: int = 0, dropped: int = 0) -> None:
        """Ends the iteration's row (written when its tokens are handed
        out, or when the next begins).  ``emitted_tokens`` defaults to
        one per active slot (plain decode); the speculative verify step
        passes the real count.  ``cache_bytes`` / ``context_tokens``
        sample the HBM the cache holds against the tokens it serves —
        the hbm_bytes_per_token series the paging win is measured on.
        ``ahead`` / ``dropped``: see ``COLUMNS``."""
        self._flush()                # the one before, if nobody emitted it
        tokens = int(emitted_tokens if emitted_tokens is not None
                     else active_slots)
        self.iterations += 1
        self.decode_tokens += tokens
        p = self._pend
        p[COL["occupancy"]] = active_slots / self.max_slots
        p[COL["decode_ms"]] = decode_s * 1e3
        p[COL["ahead"]] = ahead
        p[COL["dropped"]] = dropped
        if cache_bytes is not None and context_tokens:
            p[COL["cache_bytes"]] = int(cache_bytes)
            p[COL["context_tokens"]] = int(context_tokens)
        self._staged = self._last

    def note_block_gaps(self, gaps_s: Sequence[float]) -> None:
        """The streams handed a block now waited these seconds since their
        block before (a model generated in blocks; the scheduler's)."""
        self._block_gap_s.extend(gaps_s)

    def note_prefill(self, prompt_tokens: int) -> None:
        self.prefill_tokens += int(prompt_tokens)

    def note_admit(self, prompt_tokens: int, cached_tokens: int) -> None:
        """Prefix-cache accounting at admission: how many of the
        prompt's tokens rode already-resident blocks."""
        self.prompt_tokens_admitted += int(prompt_tokens)
        self.cached_tokens_admitted += int(cached_tokens)
        self.admissions += 1

    def note_model_counters(self, args: Dict[str, Any]) -> None:
        """One fetch's worth of the served model's own counters (the
        ``decode`` / ``prefill`` span args of a model that has any: the
        expert layer's held-row counts). Numeric ones keep a running
        mean in the snapshot (``model_counters``)."""
        for name, value in args.items():
            if isinstance(value, (int, float)):
                tot, n = self._model_counters.get(name, (0.0, 0))
                self._model_counters[name] = (tot + float(value), n + 1)

    def note_state(self, admitted: Dict[str, int],
                   totals: Dict[str, int]) -> None:
        """One admission batch into a per-stream state pool (the
        ``prefill`` span's ``resumed_tokens`` / ``state_copy_bytes`` and,
        of a model that keeps pages beside the state,
        ``prefix_lost_to_kind_tokens``, summed) and the allocator's running
        totals of snapshots taken / hit / evicted."""
        st = self._state
        for name in ("resumed_tokens", "state_copy_bytes",
                     "prefix_lost_to_kind_tokens"):
            if name in admitted:
                st[name] = st.get(name, 0) + int(admitted[name])
        st.update(totals)

    def note_admit_classes(self, returned: Dict[str, int],
                           cached: Dict[str, int]) -> None:
        """One admission batch into a model of named classes of cache
        layers: blocks each bounded class gave back while the batch's chunk
        programs were dispatched (its window slid DURING prefill:
        ``prefill_<class>_blocks_returned``) and the prompt tokens each
        class had cached (``cached_tokens_<class>``), summed over the run."""
        tot = self._admit_classes
        for name, n in returned.items():
            key = f"prefill_{name}_blocks_returned"
            tot[key] = tot.get(key, 0) + int(n)
        for name, n in cached.items():
            key = f"cached_tokens_{name}"
            tot[key] = tot.get(key, 0) + int(n)

    def note_cache_classes(self, stats: Dict[str, Dict[str, int]]) -> None:
        """A model's classes of cache layers, by name: blocks, blocks in
        use, blocks live streams returned as their window slid (the
        allocator's running totals, ``BlockAllocator.class_stats``)."""
        self._classes = {name: dict(st) for name, st in stats.items()}

    def note_spec(self, proposed: int, accepted: int) -> None:
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)

    def note_attend(self, flops_kernel: int, flops_onehot: int,
                    bytes_kernel: int, bytes_onehot: int,
                    tokens: int) -> None:
        """One iteration's analytic attend work, both ways (see
        InferenceEngine._attend_work); ``tokens`` are the iteration's
        emitted tokens — the per-token denominators."""
        self.attend_flops_kernel += int(flops_kernel)
        self.attend_flops_onehot += int(flops_onehot)
        self.attend_bytes_kernel += int(bytes_kernel)
        self.attend_bytes_onehot += int(bytes_onehot)
        self.attend_tokens += int(tokens)

    def note_attend_steps(self, steps: int, live_steps: int,
                          cold_steps: int) -> None:
        """One iteration's attend steps a layer, the live ones among them
        and the cold ones among those (InferenceEngine._attend_steps;
        zeros off the kernel)."""
        self.attend_steps += int(steps)
        self.attend_live_steps += int(live_steps)
        self.attend_cold_steps += int(cold_steps)

    def note_reject(self) -> None:
        """One reservation-gate / slot-pool admission rejection."""
        self.reservations_rejected += 1

    # ---- per completed request ---- #
    def note_request(self, ttft_s: float, tpot_s: Optional[float],
                     new_tokens: int,
                     queue_wait_s: Optional[float] = None,
                     service_ttft_s: Optional[float] = None,
                     admission_attempts: Optional[int] = None) -> None:
        """``queue_wait_s``/``service_ttft_s`` split the end-to-end TTFT
        at the admission instant (router backlog vs slow prefill —
        indistinguishable in the pooled ttft figure alone)."""
        self.completed += 1
        self._ttft_ms.append(ttft_s * 1e3)
        if tpot_s is not None:
            self._tpot_ms.append(tpot_s * 1e3)
        if queue_wait_s is not None:
            self._queue_wait_ms.append(queue_wait_s * 1e3)
        if service_ttft_s is not None:
            self._service_ttft_ms.append(service_ttft_s * 1e3)
        if admission_attempts is not None:
            self._admission_attempts.append(float(admission_attempts))

    @property
    def occupancy_mean(self) -> float:
        occ = self._table()[:, COL["occupancy"]]
        return float(occ.mean()) if len(occ) else 0.0

    def intervals(self, table: Optional[np.ndarray] = None
                  ) -> Dict[str, Any]:
        """Every (stream, consecutive token pair) of the rows held, not
        rounded: ``values`` / ``weights`` (seconds; a row's interval for
        its continuing streams, and the mean first interval of those it
        admitted), their count ``n`` and ``total_s``, the total's parts
        ``decode_wait_s`` (``dispatch_s`` + ``fetch_s``), ``stall_s``
        (``prefill_s`` + ``copy_s``: other requests' admissions) and
        ``host_s`` (the rest), and ``stalled``: how many held a prefill
        dispatch."""
        t = self._table() if table is None else table

        def col(name):
            return t[:, COL[name]]
        cont, adm = col("continuing"), col("admitted")
        first = np.divide(col("first_gap_s"), adm,
                          out=np.zeros(len(t)), where=adm > 0)
        total = float(cont @ col("gap_s") + col("first_gap_s").sum())
        wait = float((cont + adm) @ (col("dispatch_s") + col("fetch_s")))
        stall = float(cont @ (col("prefill_s") + col("copy_s"))
                      + col("first_stall_s").sum())
        held = col("prefill_dispatches") > 0
        return {"values": np.concatenate([col("gap_s"), first]),
                "weights": np.concatenate([cont, adm]),
                "n": int(cont.sum() + adm.sum()), "total_s": total,
                "decode_wait_s": wait, "stall_s": stall,
                "host_s": total - wait - stall,
                "stalled": int(cont[held].sum()
                               + adm[col("first_stall_s") > 0].sum())}

    def stalls(self, table: Optional[np.ndarray] = None
               ) -> List[Dict[str, Any]]:
        """The latest ``serve()``'s worst intervals: the rows
        ``stall_rows`` keeps (at most ``STALLS_KEPT``, the longest, in
        order of time) of those whose interval, waited by at least one
        stream, exceeded the larger of ``STALL_TIMES_MEDIAN`` x the
        median interval and ``STALL_FLOOR_S``; each with its row, the
        seconds since the serve began, and the part of the interval
        (``GAP_PARTS``) that held most of the excess over that part's
        median."""
        t = self._table() if table is None else table
        index = np.arange(self._n - len(t), self._n)
        keep = (index >= self._serve_row0) & (t[:, COL["continuing"]] > 0)
        t, index = t[keep], index[keep]
        if not len(t):
            return []
        gap = t[:, COL["gap_s"]]
        parts = [COL[c] for c in GAP_PARTS]
        excess = t[:, parts] - np.median(t[:, parts], axis=0)
        names = list(GAP_PARTS.values())
        return [{"row": int(index[i]),
                 "at_s": round(float(t[i, COL["t_emit"]])
                               - self._serve_t0, 3),
                 "gap_ms": round(float(gap[i]) * 1e3, 3),
                 "in": names[int(np.argmax(excess[i]))],
                 "in_ms": round(float(excess[i].max()) * 1e3, 3)}
                for i in stall_rows(gap)]

    def snapshot(self, wall_s: Optional[float] = None) -> Dict[str, Any]:
        """The canonical serving summary. ``tokens_per_s`` counts
        GENERATED (decode) tokens over the serve wall — prefill tokens
        are reported separately, not inflated into throughput. Fields
        the engine never fed (no paged cache, no spec decode) are
        omitted so pre-paging consumers and the bench gate's
        skip-never-fail rule keep working. ``attend_live_step_share``
        (paged kernel only) is the share of the attend's sequencing
        steps that touched a live block, over all iterations so far: the
        rest are the empty steps of dead streams;
        ``attend_cold_step_share`` the share of those live steps whose
        first copies the step before had not started (the first of a
        call, and one after a dead stream).

        From the rows (the last ``RING`` iterations): ``occupancy_*``,
        ``decode_step_ms``, ``hbm_bytes_per_token``, ``cache_bytes_p95``
        and, once a stream waited an interval, ``itl_ms`` (every
        stream's inter-token interval: p50 / p95 / p99 / max / mean over
        ``n`` of them), ``itl_split_ms`` (the mean interval's parts
        ``decode_wait``, ``stall``, ``host``: they sum to it),
        ``itl_stalled_share`` (intervals that held a prefill dispatch),
        ``prefill_row_fill`` (rows the prefills needed / rows their
        dispatches computed), ``prefill_width_dispatches`` (``{row
        width: chunk programs dispatched at it}``, over the whole run),
        ``stalls`` (see ``stalls()``),
        ``lookahead_share`` (iterations dispatched while the one before
        was still unfetched: the host's pass hid under the device) and
        ``lookahead_dropped_rows`` (rows computed for streams that had
        ended: at most one a stream that stops on an EOS)."""
        wall = wall_s if wall_s is not None else self.clock() - self.t0
        table = self._table()
        occupancy = table[:, COL["occupancy"]].tolist()
        sampled = table[table[:, COL["context_tokens"]] > 0]
        snap = {
            "iterations": self.iterations,
            "completed": self.completed,
            "occupancy_mean": round(
                sum(occupancy) / len(occupancy) if occupancy else 0.0, 4),
            "occupancy_p50": round(
                percentile(sorted(occupancy), 50), 4),
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "tokens_per_s": round(self.decode_tokens / wall, 3)
            if wall > 0 else 0.0,
            "wall_s": round(wall, 6),
            "ttft_ms": _pcts(self._ttft_ms),
            "tpot_ms": _pcts(self._tpot_ms),
            "decode_step_ms": _pcts(table[:, COL["decode_ms"]].tolist()),
        }
        if self.label is not None:
            snap["replica"] = self.label
        if self._queue_wait_ms:
            snap["queue_wait_ms"] = _pcts(self._queue_wait_ms)
        if self._service_ttft_ms:
            snap["service_ttft_ms"] = _pcts(self._service_ttft_ms)
        if self.reservations_rejected or self._admission_attempts:
            snap["admission"] = {
                "reservations_rejected": self.reservations_rejected,
                "attempts": _pcts(self._admission_attempts),
            }
        if self.ledger is not None:
            snap["ledger"] = self.ledger.snapshot(wall_s=wall) \
                if hasattr(self.ledger, "snapshot") else self.ledger
        if self.slo is not None:
            slo = self.slo.snapshot() if hasattr(self.slo, "snapshot") \
                else self.slo
            if slo is not None:
                snap["slo"] = slo
        if len(sampled):
            held = sampled[:, COL["cache_bytes"]]
            snap["hbm_bytes_per_token"] = _pcts(
                (held / sampled[:, COL["context_tokens"]]).tolist())
            snap["cache_bytes_p95"] = int(percentile(
                sorted(held.tolist()), 95))
        itl = self.intervals(table)
        if itl["n"]:
            v, w, n = itl["values"], itl["weights"], itl["n"]
            snap["itl_ms"] = {
                **{f"p{q}": round(1e3 * weighted_percentile(v, w, q), 3)
                   for q in (50, 95, 99)},
                "max": round(1e3 * float(v[w > 0].max()), 3),
                "mean": 1e3 * itl["total_s"] / n, "n": n}
            snap["itl_split_ms"] = {
                "decode_wait": 1e3 * itl["decode_wait_s"] / n,
                "stall": 1e3 * itl["stall_s"] / n,
                "host": 1e3 * itl["host_s"] / n}
            snap["itl_stalled_share"] = round(itl["stalled"] / n, 4)
            snap["stalls"] = self.stalls(table)
        if self._block_gap_s:
            gaps = sorted(1e3 * g for g in self._block_gap_s)
            snap["block_gap_ms"] = {
                **{f"p{q}": round(percentile(gaps, q), 3)
                   for q in (50, 95, 99)},
                "mean": round(sum(gaps) / len(gaps), 3), "n": len(gaps)}
        if len(table):
            snap["lookahead_share"] = round(
                float(table[:, COL["ahead"]].mean()), 4)
            snap["lookahead_dropped_rows"] = int(
                table[:, COL["dropped"]].sum())
        computed = float(table[:, COL["prefill_rows_computed"]].sum())
        if computed:
            snap["prefill_row_fill"] = round(
                float(table[:, COL["prefill_rows"]].sum()) / computed, 4)
        if self.prefill_width_dispatches:
            snap["prefill_width_dispatches"] = dict(sorted(
                self.prefill_width_dispatches.items()))
        if self.prompt_tokens_admitted:
            snap["prefix"] = {
                "prompt_tokens": self.prompt_tokens_admitted,
                "cached_tokens": self.cached_tokens_admitted,
                "hit_rate": round(self.cached_tokens_admitted /
                                  self.prompt_tokens_admitted, 4),
                "admissions": self.admissions,
                "chain_walks": self.chain_walks,
            }
        if self.spec_proposed:
            snap["spec"] = {
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "acceptance_rate": round(self.spec_accepted /
                                         self.spec_proposed, 4),
            }
        if self._state:
            snap["state"] = dict(self._state)
        if self._classes:
            snap["cache_classes"] = {n: dict(st)
                                     for n, st in self._classes.items()}
        snap.update(self._admit_classes)
        if self._model_counters:
            snap["model_counters"] = {
                name: round(tot / n, 4)
                for name, (tot, n) in self._model_counters.items()}
        if self.attend_tokens:
            t = self.attend_tokens
            snap["attend"] = {
                "mode": self.attend_mode or "onehot",
                "flops_per_token": {
                    "kernel": round(self.attend_flops_kernel / t, 1),
                    "onehot": round(self.attend_flops_onehot / t, 1)},
                "hbm_bytes_per_token": {
                    "kernel": round(self.attend_bytes_kernel / t, 1),
                    "onehot": round(self.attend_bytes_onehot / t, 1)},
                "projection": "analytic (host-priced, not a device "
                              "measurement)",
            }
            if self.attend_bytes_kernel:
                # The structural headline: one-hot HBM traffic over the
                # kernel's, same iterations — >1 means the pool
                # outweighs the live contexts it served.
                snap["attend_work_ratio"] = round(
                    self.attend_bytes_onehot / self.attend_bytes_kernel,
                    4)
        if self.attend_steps:
            snap["attend_live_step_share"] = round(
                self.attend_live_steps / self.attend_steps, 4)
        if self.attend_live_steps:
            snap["attend_cold_step_share"] = round(
                self.attend_cold_steps / self.attend_live_steps, 4)
        return snap

    @classmethod
    def merged(cls, aggs: List["ServingAggregator"],
               label: str = "aggregate") -> "ServingAggregator":
        """The honest aggregate over replicas: raw samples POOLED, not
        percentiles-of-percentiles, counters summed, capacity summed."""
        out = cls(sum(a.max_slots for a in aggs) or 1, label=label)
        for a in aggs:
            out.iterations += a.iterations
            out.decode_tokens += a.decode_tokens
            out.prefill_tokens += a.prefill_tokens
            out.prefill_width_dispatches += a.prefill_width_dispatches
            out.completed += a.completed
            out.prompt_tokens_admitted += a.prompt_tokens_admitted
            out.cached_tokens_admitted += a.cached_tokens_admitted
            out.admissions += a.admissions
            out.chain_walks += a.chain_walks
            for key, n in a._admit_classes.items():
                out._admit_classes[key] = out._admit_classes.get(key, 0) + n
            out.spec_proposed += a.spec_proposed
            out.spec_accepted += a.spec_accepted
            out.attend_flops_kernel += a.attend_flops_kernel
            out.attend_flops_onehot += a.attend_flops_onehot
            out.attend_bytes_kernel += a.attend_bytes_kernel
            out.attend_bytes_onehot += a.attend_bytes_onehot
            out.attend_tokens += a.attend_tokens
            out.attend_steps += a.attend_steps
            out.attend_live_steps += a.attend_live_steps
            out.attend_cold_steps += a.attend_cold_steps
            if out.attend_mode is None:
                out.attend_mode = a.attend_mode
            # Occupancy normalizes per-replica (active/its own slots):
            # pooling the normalized rows keeps the mean meaningful
            # as "fraction of owned capacity busy".
            out._extend_rows(a._table())
            out._block_gap_s.extend(a._block_gap_s)
            out._ttft_ms.extend(a._ttft_ms)
            out._tpot_ms.extend(a._tpot_ms)
            out._queue_wait_ms.extend(a._queue_wait_ms)
            out._service_ttft_ms.extend(a._service_ttft_ms)
            out._admission_attempts.extend(a._admission_attempts)
            out.reservations_rejected += a.reservations_rejected
        out._serve_row0 = out.rows      # a replica's stalls are its own
        # Fleet-level SLO/ledger views: pooled outcomes and bucket-wise
        # sums, stored as settled dicts (a merged aggregator keeps
        # accumulating nothing).
        from .serving_slo import ServingGoodputLedger, SLOTracker
        trackers = [a.slo for a in aggs if isinstance(a.slo, SLOTracker)]
        if trackers:
            out.slo = SLOTracker.merged(trackers)
        led = [a.ledger.snapshot() for a in aggs
               if a.ledger is not None and hasattr(a.ledger, "snapshot")]
        led += [a.ledger for a in aggs if isinstance(a.ledger, dict)]
        if led:
            out.ledger = ServingGoodputLedger.merged(led)
        return out


__all__ = ["ServingAggregator", "percentile", "COLUMNS", "GAP_PARTS",
           "stall_limit", "stall_rows"]

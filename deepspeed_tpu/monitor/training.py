"""The training loop's timeline: one row a ``train_batch`` call, always on.

The training twin of the serving timeline (``monitor/serving.py``): the
engine hands its clock to ``enter`` / ``lap`` / ``leave`` at the
boundaries its spans already have, so the time from one call's entry to
the next one's is split, without a remainder, into the columns of two
neighbouring rows — the call's own ``data_s`` + ``dispatch_s`` +
``log_s`` and the next row's ``outside_s`` (the user's code between the
calls) sum to the next row's ``gap_s``.

Steps are seen complete WITHOUT a sync: the loss futures of the steps in
flight wait in a deque, and at each call's entry and exit the timeline
pops from the left while ``jax.Array.is_ready()`` is true.  It never
calls ``block_until_ready``, ``device_get`` or ``float()``; completion is
so quantised to the loop's own calls, which is what a stall needs: how
many steps finished while the thread was away.

An interval over the stall rule (``monitor.serving.stall_limit``: the one
rule of both timelines) is logged once, by the row that closes it, with
where the thread stood and whether the device's queue drained meanwhile
(``snapshot()["stalls"]``).
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List

import numpy as np

from .serving import (RING, STALL_FLOOR_S, percentile, stall_limit,
                      stall_rows)
from ..utils.logging import logger

# One row per ``train_batch`` call.  ``t_enter`` and ``t_complete`` are
# the clock's readings, the ``*_s`` columns seconds between two of them:
# ``gap_s`` from the call before's entry to this one's, of which
# ``outside_s`` since that call returned; ``data_s`` / ``dispatch_s`` /
# ``log_s`` this call's three child spans (``log_s`` holds ``save_s``, the
# exposed wall of the checkpoint spans inside it).  ``in_flight``: earlier
# steps dispatched and not yet seen complete when this one is dispatched;
# ``completed``: steps first seen complete since the entry before (the
# polls of that call's exit and of this entry: the ones inside
# ``gap_s``); ``t_complete``: when this step was first SEEN complete (0
# until then); ``built``: step programs built or compiled inside the call.
COLUMNS = ("step", "t_enter", "gap_s", "outside_s", "data_s", "dispatch_s",
           "log_s", "in_flight", "completed", "t_complete", "save_s",
           "built")
COL = {name: i for i, name in enumerate(COLUMNS)}
# Where the thread can stand in an interval, under the names an operator
# knows (the host spans'; ``outside`` is no span's: the caller's code).
# The first three are the call's that BEGAN the interval, after whose
# dispatch the step itself is in flight too.
WHERE = ("data_prep", "step_dispatch", "step_log", "checkpoint_save",
         "outside")
LIVE_ROWS = 1024             # intervals the log's median is taken over


class TrainingTimeline:
    """The rows of one engine's ``train_batch`` calls (``engine.timeline``).

    ``clock`` is the one clock of the rows: a test that drives it drives
    every stamp.  A ring of ``monitor.serving.RING`` rows is kept.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.t0 = clock()
        self._rows = np.zeros((RING, len(COLUMNS)))
        self._n = 0                       # rows written so far
        self._pend = [0.0] * len(COLUMNS)
        self._written = self._pend        # the latest row, as a list
        self._last = self.t0              # the clock at the latest lap
        # (row, loss) of the steps dispatched and not yet seen complete,
        # oldest first, and how many the latest exit's poll saw complete.
        self._flying: collections.deque = collections.deque()
        self._seen_at_exit = 0
        self.built_after_first = 0
        self.stalls_logged = 0

    # ---- the rows ---- #
    @property
    def rows(self) -> int:
        """Rows written so far (the index of the next)."""
        return self._n

    def _poll(self) -> List[int]:
        """Rows of the steps first seen complete now, oldest first.  A
        loss that is a host value (the offload path's) is complete at
        once."""
        flying, rows = self._flying, []
        while flying:
            ready = getattr(flying[0][1], "is_ready", None)
            if ready is not None and not ready():
                break
            rows.append(flying.popleft()[0])
        return rows

    def _stamp(self, rows: List[int], now: float) -> None:
        if rows:
            # A row the ring has dropped meanwhile is not written to.
            live = [r % RING for r in rows if r >= self._n - RING]
            self._rows[live, COL["t_complete"]] = now

    def enter(self, step: int) -> float:
        """A ``train_batch`` call begins: reads the clock, sees what
        completed, opens the call's row; returns the reading."""
        now = self.clock()
        done = self._poll()
        self._stamp(done, now)
        p = self._pend = [0.0] * len(COLUMNS)   # a call that raised left one
        p[COL["step"]] = step
        p[COL["t_enter"]] = now
        if self._n:
            p[COL["gap_s"]] = now - self._written[COL["t_enter"]]
            p[COL["outside_s"]] = now - self._last
        p[COL["completed"]] = self._seen_at_exit + len(done)
        self._last = now
        return now

    def lap(self, column: str) -> float:
        """Read the clock and file the time since the last reading under
        ``column`` of the open row; returns the reading."""
        now = self.clock()
        self._pend[COL[column]] += now - self._last
        self._last = now
        return now

    def dispatched(self, loss: Any, built: int = 0) -> float:
        """The step went out (a lap of ``dispatch_s``): ``loss`` is its
        future, kept until it is seen complete; ``built`` the programs
        the call built or compiled."""
        p = self._pend
        p[COL["in_flight"]] = len(self._flying)
        p[COL["built"]] = built
        if self._n:
            self.built_after_first += int(built)
        self._flying.append((self._n, loss))
        return self.lap("dispatch_s")

    @property
    def wall_s(self) -> float:
        """The open row's seconds so far, from the call's entry to its
        latest lap."""
        return self._last - self._pend[COL["t_enter"]]

    def leave(self, save_s: float = 0.0) -> int:
        """The call ends (a lap of ``log_s``, of which ``save_s`` in
        checkpoint spans): sees what completed, writes the row, logs the
        stall it closes if it closes one; returns the row's index."""
        done = self._poll()
        self._seen_at_exit = len(done)
        p = self._pend
        p[COL["save_s"]] = save_s
        self._stamp(done, self.lap("log_s"))
        row = self._n
        if row in done:                  # complete before the call ended
            p[COL["t_complete"]] = self._last
        self._rows[row % RING] = p
        self._n += 1
        self._written = p
        if p[COL["gap_s"]] > STALL_FLOOR_S:
            self._log_if_stalled()
        return row

    def span_args(self) -> Dict[str, Any]:
        """The ``train_batch`` span's args of the row ``leave`` has just
        written (for a span that something records: the engine does not
        build them otherwise), times in ms: the interval since the entry
        before, ``gap_ms``, of which ``outside_ms`` since that call
        returned; this call's ``data_ms`` + ``dispatch_ms`` + ``log_ms``
        = ``host_ms``; and the row's counts."""
        p = self._written
        data, dispatch, log = (p[COL[c]] for c in
                               ("data_s", "dispatch_s", "log_s"))
        return {"row": self._n - 1,
                "gap_ms": round(p[COL["gap_s"]] * 1e3, 4),
                "outside_ms": round(p[COL["outside_s"]] * 1e3, 4),
                "host_ms": round((data + dispatch + log) * 1e3, 4),
                "data_ms": round(data * 1e3, 4),
                "dispatch_ms": round(dispatch * 1e3, 4),
                "log_ms": round(log * 1e3, 4),
                "in_flight": int(p[COL["in_flight"]]),
                "completed": int(p[COL["completed"]]),
                "built": int(p[COL["built"]])}

    def table(self, latest: int = RING) -> np.ndarray:
        """The ``latest`` rows held, oldest first (``COLUMNS``)."""
        n = self._n
        held = min(n, RING, latest)
        return self._rows[np.arange(n - held, n) % RING]

    # ---- stalls ---- #
    def _judged(self, t: np.ndarray):
        """Of the rows ``t``, the latest held: (``k``, the seconds of the
        intervals the rows ``t[k:]`` close, which of them are the USUAL
        ones a stall is held against).  Every row closes one but the
        oldest, and but the one after the timeline's first where that one
        built the step — start-up, which every run has (``k`` 2).  The
        usual ones are those by whose end a step was seen complete: the
        loop went at the device's pace there, where a loop that is
        filling its queue goes at the host's, a hundred times faster, and
        its first wait for a loss would read as a stall against those."""
        k = 2 if self._n == len(t) and len(t) and \
            t[0, COL["built"]] > 0 else 1
        return k, t[k:, COL["gap_s"]], t[k:, COL["completed"]] > 0

    def _stall(self, cur: np.ndarray, prev: np.ndarray, row: int,
               median_s: float) -> Dict[str, Any]:
        """The interval ``cur`` closes and ``prev`` began, as a record."""
        save = prev[COL["save_s"]]
        parts = (prev[COL["data_s"]], prev[COL["dispatch_s"]],
                 prev[COL["log_s"]] - save, save, cur[COL["outside_s"]])
        where = WHERE[int(np.argmax(parts))]
        # The step the interval began with is in the queue too, unless
        # the thread stood before it went out.
        before = int(prev[COL["in_flight"]]) + \
            (where not in ("data_prep", "step_dispatch"))
        during, gap = int(cur[COL["completed"]]), float(cur[COL["gap_s"]])
        verdict = "host" if during >= before else \
            "device" if during == 0 else "both"
        return {"row": row, "step": int(prev[COL["step"]]),
                "t": round(float(prev[COL["t_enter"]]) - self.t0, 3),
                "gap_s": round(gap, 6), "median_s": round(median_s, 6),
                "where": where, "in_flight_before": before,
                "completed_during": during, "verdict": verdict,
                "built": int(prev[COL["built"]]),
                "device_lost_s": round(max(gap - during * median_s, 0.0), 6)}

    def stalls(self) -> List[Dict[str, Any]]:
        """The worst intervals of the rows held: those ``stall_rows``
        keeps (entry to entry over the larger of ``STALL_TIMES_MEDIAN``
        medians and ``STALL_FLOOR_S``, the median that of the intervals
        by whose end a step was seen complete; the ``STALLS_KEPT``
        longest, in order of time), each with the ``row`` that closed it, the
        ``step`` whose call began it, ``t`` (seconds from the timeline's
        start to the interval's), ``gap_s`` beside the ``median_s`` it
        was held against, ``where`` the largest part of it lay (``WHERE``),
        ``in_flight_before`` (steps the device had to work on),
        ``completed_during`` (those seen complete by the interval's end),
        ``built`` (programs the call built) and the ``verdict``: ``host``
        where the queue drained (the thread held the device back),
        ``device`` where nothing completed (the device or the runtime
        stood while the host waited), ``both`` otherwise;
        ``device_lost_s``, the interval less a median a step completed in
        it, is what the device did not work of it, to a median either
        way."""
        t = self.table()
        k, gap, usual = self._judged(t)
        if not usual.any():
            return []
        median = float(np.median(gap[usual]))
        return [self._stall(t[k + i], t[k + i - 1],
                            self._n - len(t) + k + int(i), median)
                for i in stall_rows(gap, usual)]

    def _log_if_stalled(self) -> None:
        """The row just written closed an interval over ``STALL_FLOOR_S``:
        where it is a stall by the rule over the latest ``LIVE_ROWS``
        intervals, it is logged, once."""
        t = self.table(LIVE_ROWS + 1)
        _, gap, usual = self._judged(t)
        if not usual.any() or gap[-1] <= stall_limit(gap[usual]):
            return
        st = self._stall(t[-1], t[-2], self._n - 1,
                         float(np.median(gap[usual])))
        self.stalls_logged += 1
        logger.warning(
            "train_batch: stalled interval: row %d (from step %d, %.1f s "
            "in) took %.1f ms where the median is %.1f, most of it in %s; "
            "%d step(s) in flight before, %d seen complete by its end: "
            "%s (%s; ~%.2f s of device time lost)",
            st["row"], st["step"], st["t"], st["gap_s"] * 1e3,
            st["median_s"] * 1e3, st["where"]
            + (f" ({st['built']} program(s) built)" if st["built"] else ""),
            st["in_flight_before"], st["completed_during"], st["verdict"],
            {"host": "the queue drained: the thread held the device back",
             "device": "nothing completed: the device or the runtime stood "
                       "while the host waited",
             "both": "the queue neither drained nor stood"}[st["verdict"]],
            st["device_lost_s"])

    # ---- the summary ---- #
    def snapshot(self) -> Dict[str, Any]:
        """The canonical summary of the rows held, keys as
        ``ServingAggregator.snapshot()`` names its own where they mean
        the same: ``steps`` (rows written), ``gap_ms`` (entry to entry:
        p50 / p95 / p99 / max), ``host_ms`` (inside the engine a call:
        ``mean`` and ``p99`` of ``data`` + ``dispatch`` + ``log``, and
        the three parts' means), ``outside_ms`` (mean), ``in_flight``
        (mean, min), ``built_after_first`` (programs built or compiled by
        any call but the first), ``stalls`` (see ``stalls()``) and
        ``stall_s_total`` (seconds of EVERY interval over the rule beyond
        the median interval).  The first call is left out of all of them
        where it built the step (start-up: ``_judged``)."""
        t = self.table()
        snap: Dict[str, Any] = {"steps": self._n,
                                "built_after_first": self.built_after_first}
        k, gap, usual = self._judged(t)
        t = t[k - 1:]       # the call that built the step: start-up here too
        if not len(t):
            return snap

        def ms(name):
            return t[:, COL[name]] * 1e3
        host = ms("data_s") + ms("dispatch_s") + ms("log_s")
        snap["host_ms"] = {
            "mean": round(float(host.mean()), 4),
            "p99": round(percentile(sorted(host.tolist()), 99), 4),
            "data": round(float(ms("data_s").mean()), 4),
            "dispatch": round(float(ms("dispatch_s").mean()), 4),
            "log": round(float(ms("log_s").mean()), 4)}
        flying = t[:, COL["in_flight"]]
        snap["in_flight"] = {"mean": round(float(flying.mean()), 3),
                             "min": int(flying.min())}
        if not len(gap):
            return snap
        gap_ms = sorted((gap * 1e3).tolist())
        snap["gap_ms"] = {
            **{f"p{q}": round(percentile(gap_ms, q), 3)
               for q in (50, 95, 99)}, "max": round(gap_ms[-1], 3)}
        snap["outside_ms"] = {"mean": round(float(ms("outside_s")[1:].mean()),
                                            4)}
        snap["stalls"] = self.stalls()
        snap["stall_s_total"] = round(float(
            (gap[gap > stall_limit(gap[usual])] - np.median(gap[usual])).sum()
        ), 6) if usual.any() else 0.0
        return snap


__all__ = ["TrainingTimeline", "COLUMNS", "WHERE"]

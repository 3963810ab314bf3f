"""The start-up ledger: what this process built before it served.

One process-wide, always-on, bounded list of rows on ONE clock: seconds
of PROCESS AGE (``time.perf_counter()`` shifted once by the process's
start time from ``/proc/self/stat``), so an operator reads "time since
launch" and a reader can cut the list at any age.  Kinds of row:

- ``before_program``: process start to the first statement of
  ``deepspeed_tpu/__init__.py`` (the interpreter, ``import jax``,
  ``jax.devices()``: the box's share);
- ``package_import``: that statement to the package's last (``backend_up``:
  whether ``jax.devices()`` had run by then), and one a part of the
  package that loads at first use (``part``);
- ``engine_init`` (one a constructor of ``InferenceEngine`` /
  ``DeepSpeedEngine``; args ``mode``, ``param_bytes``, ``cache_bytes``)
  with children ``place_params``, ``allocate_cache`` / ``shard_state``,
  and ``warm_prefill_widths``: spans the program brackets itself
  (``span``), each also a ``jax.profiler.TraceAnnotation`` carrying
  ``age_s``, its start on this clock;
- ``program_build``, one a program and abstract signature: ``program``,
  ``trace_s``, ``lower_s``, ``backend_s``, ``source`` (``compiled``: a
  compile-cache miss | ``compile_cache``: a persistent-cache hit |
  ``kept_executable``: a file ``_WidthPrograms`` deserialised, ``bytes``
  its size), ``own`` (1 for a function an engine registered, 0 for any
  other jitted function of the process) and what ``build_args`` adds
  (``width``).  They come from JAX's own monitoring events, which fire
  only when something is traced, lowered or compiled: a steady window
  pays nothing.  A build after start-up is a row like any other;
- ``engine_traffic``: one a call of ``InferenceEngine.serve()``
  (``traffic``) and, derived at ``snapshot()`` from the training
  timelines' rows, one a ``train_batch`` call.

``snapshot()`` is what the engines' reports carry under ``"startup"``.
See docs/tutorials/telemetry.md ("Start-up").
"""
from __future__ import annotations

import collections
import functools
import os
import re
import threading
import time
import weakref
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

from .. import _IMPORT_CLOCK        # perf_counter at the package's first
#                                     statement

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
STAGES = (TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
KEPT = 2048                  # rows held: the first KEPT and the latest KEPT
TRAFFIC_CALLS = 512          # train_batch calls a snapshot turns into rows


def _age_at_import() -> Optional[float]:
    """Process age at the package's first statement, or None where
    ``/proc`` does not say (the clock then starts at that statement)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - start_ticks / os.sysconf("SC_CLK_TCK") \
            - (time.perf_counter() - _IMPORT_CLOCK)
        return age if 0.0 <= age < 3e7 else None
    except Exception:
        return None


_AGE = _age_at_import()
PERF_ORIGIN = _IMPORT_CLOCK - (_AGE or 0.0)   # perf_counter at age 0

class _Rows:
    """The first ``kept`` rows (a process's start-up: they stay) and, of
    the rows after them, the latest ``kept``."""

    def __init__(self, kept: int):
        self.head: List[Dict[str, Any]] = []
        self.tail: collections.deque = collections.deque(maxlen=kept)
        self.dropped = 0
        self._lock = threading.Lock()

    def add(self, row: Dict[str, Any]) -> Dict[str, Any]:
        with self._lock:
            if len(self.head) < self.tail.maxlen:
                self.head.append(row)
            else:
                self.dropped += len(self.tail) == self.tail.maxlen
                self.tail.append(row)
        return row

    def all(self) -> List[Dict[str, Any]]:
        with self._lock:
            return self.head + list(self.tail)


_rows = _Rows(KEPT)
_own: Dict[str, str] = {}          # jitted function's name -> program
_own_builds = 0
_first_useful_s: Optional[float] = None
# The training engines attached last, each with the timeline it had: a
# snapshot reads an engine's rows after the engine itself has gone.
_engines: collections.deque = collections.deque(maxlen=4)
_local = threading.local()   # .depth, .traced, .lowered, .hit, .args
_BARE = re.compile(r"^\w+\((.*)\)$")
_add = _rows.add


def now() -> float:
    """This process's age in seconds."""
    return time.perf_counter() - PERF_ORIGIN


def rows() -> List[Dict[str, Any]]:
    """The rows held, in the order they were written (a row is written
    when what it describes ENDS)."""
    return _rows.all()


def register_program(fn: Any, program: str) -> None:
    """An engine's own compiled function: builds of a jitted function of
    its name are ``own`` rows named ``program``."""
    _own[getattr(fn, "__name__", program)] = program


def own_builds() -> int:
    """``own`` build rows written so far, in the whole process (one
    integer: ``train_batch`` reads it round its dispatch)."""
    return _own_builds


def build_seconds(program: str, since_s: float) -> Optional[float]:
    """Seconds of the ``program_build`` rows named ``program`` that ended
    at or after ``since_s``; None where there is none."""
    found = [r["trace_s"] + r["lower_s"] + r["backend_s"]
             for r in rows() if r["kind"] == "program_build"
             and r["program"] == program and r["end_s"] >= since_s]
    return sum(found) if found else None


@contextmanager
def build_args(**args) -> Iterator[None]:
    """Builds of this thread inside the block carry ``args`` (a width)."""
    before, _local.args = getattr(_local, "args", {}), args
    try:
        yield
    finally:
        _local.args = before


@contextmanager
def span(name: str, kind: Optional[str] = None, **args
         ) -> Iterator[Dict[str, Any]]:
    """What the program brackets itself: the profiler annotation
    ``Telemetry.span`` opens (``args`` and ``age_s``, the start on this
    clock, ride on it) and, at its end, a row of ``kind`` (``name``'s
    own by default) with ``args`` and whatever the block put into the
    dict it is handed.  A block that raises leaves no row."""
    start = now()
    row = dict(kind=kind or name, start_s=start, **args)
    with TraceAnnotation(name, age_s=start, **args) as ann:
        yield row
        late = {k: v for k, v in row.items()
                if k not in args and k not in ("kind", "start_s")}
        if late:
            ann.set_metadata(**late)
    row["end_s"] = now()
    _add(row)


def engine_init(mode: str):
    """Decorator of an engine's constructor: one ``engine_init`` row (and
    span) a construction, closed with the engine's ``_startup_args()``;
    an engine with a ``timeline`` (training) is kept for ``snapshot()``,
    which turns its ``train_batch`` rows into ``engine_traffic``."""
    def wrap(init):
        @functools.wraps(init)
        def constructor(self, *args, **kwargs):
            with span("engine_init", mode=mode) as row:
                init(self, *args, **kwargs)
                row.update(self._startup_args())
            if hasattr(self, "timeline"):
                _engines.append((weakref.ref(self), self.timeline))
        return constructor
    return wrap


def kept_executable(row: Dict[str, Any]) -> None:
    """Close the ``program_build`` row of an executable that was loaded
    from a file and not built (called inside its ``span``)."""
    global _own_builds
    row.update(trace_s=0.0, lower_s=0.0, backend_s=now() - row["start_s"],
               source="kept_executable", own=1)
    _own_builds += 1


def traffic(start_s: float, first_token_s: Optional[float] = None,
            **args) -> None:
    """A ``serve()`` call's ``engine_traffic`` row, from ``start_s`` to
    now; ``first_token_s``: when it handed out its first token."""
    global _first_useful_s
    _add(dict(kind="engine_traffic", start_s=start_s, end_s=now(), **args))
    if first_token_s is not None and (_first_useful_s is None
                                      or first_token_s < _first_useful_s):
        _first_useful_s = first_token_s


# ---- JAX's events -> program_build rows ---- #
def _on_start(event: str, _value: float, **_kw) -> None:
    """A trace, lowering or backend stage BEGINS in this thread."""
    if event in STAGES:
        _local.depth = getattr(_local, "depth", 0) + 1


def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_kw) -> None:
    """... and ENDS.  A function traced inside another's trace, and what
    a lowering traces or builds on its way, end inside the outer stage
    and inside its seconds: only the outermost stages make the row."""
    if event not in STAGES:
        return
    _local.depth = depth = max(getattr(_local, "depth", 1) - 1, 0)
    if depth:
        return
    name = _BARE.sub(r"\1", fun_name)
    if event == TRACE_EVENT:
        _local.traced = (name, start, end)
        return
    traced, _local.traced = getattr(_local, "traced", None), None
    if event == LOWER_EVENT:
        # Its trace is the one just before it (none: jit's trace cache
        # held it, or the last one traced was never lowered).
        if traced is not None and (traced[0] != name or start - traced[2]
                                   > max(2.0, 2 * (traced[2] - traced[1]))):
            traced = None
        _local.lowered = (name, traced, start, end)
    else:
        _close_build(name, start, end)


def _close_build(name: str, start: float, end: float) -> None:
    global _own_builds
    hit, _local.hit = getattr(_local, "hit", False), False
    lowered, _local.lowered = getattr(_local, "lowered", None), None
    trace_s = lower_s = 0.0
    first = start
    if lowered is not None and lowered[0] == name:
        _, traced, l0, l1 = lowered
        lower_s, first = l1 - l0, l0
        if traced is not None:
            trace_s, first = traced[2] - traced[1], traced[1]
    own = name in _own
    # JAX's stamps are ``time.time()``'s: only their differences are
    # used, so a wall clock that is slewed moves no row.
    end_s = now()
    row = _add(dict(
        kind="program_build", program=_own.get(name, name),
        start_s=end_s - (end - first), end_s=end_s,
        trace_s=trace_s, lower_s=lower_s, backend_s=end - start,
        source="compile_cache" if hit else "compiled", own=int(own),
        **getattr(_local, "args", {})))
    _own_builds += own
    if TraceAnnotation.is_enabled():
        # A stage cannot be bracketed (JAX says when it has ENDED): an
        # instant marker ties the profiler's clock to this one.
        with TraceAnnotation("program_build", program=row["program"],
                             age_s=now(), build_s=row["end_s"]
                             - row["start_s"], source=row["source"]):
            pass


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _local.hit = True


def imported(part: str, start_s: float) -> None:
    """A part of the package that loads at first use: a
    ``package_import`` row of its own."""
    _add(dict(kind="package_import", part=part, start_s=start_s,
              end_s=now()))


def package_imported() -> None:
    """The package's last statement: the first two rows, and the
    listeners (they run only when something is traced or compiled)."""
    from jax._src import xla_bridge
    if _AGE is not None:
        _add(dict(kind="before_program", start_s=0.0, end_s=_AGE))
    # backend_up: whether ``jax.devices()`` had run by now (1: its
    # seconds lie in ``before_program``; 0: in whatever first asks for a
    # device, an engine's constructor or the caller's own code).
    _add(dict(kind="package_import", start_s=_AGE or 0.0, end_s=now(),
              backend_up=int(xla_bridge.backends_are_initialized())))
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_listener(_on_event)


# ---- the summary ---- #
def _training_traffic() -> List[Dict[str, Any]]:
    """``engine_traffic`` rows of the attached engines' ``train_batch``
    calls, from their timelines' rows (the first ``TRAFFIC_CALLS`` held):
    a call's entry to its step first SEEN complete (or the call's end)."""
    from .training import COL
    out = []
    for ref, tm in list(_engines):
        tm = getattr(ref(), "timeline", tm)    # (an engine may swap its own)
        if tm.clock is not time.perf_counter:
            continue
        for r in tm.table()[:TRAFFIC_CALLS]:
            enter = r[COL["t_enter"]]
            left = enter + r[COL["data_s"]] + r[COL["dispatch_s"]] \
                + r[COL["log_s"]]
            out.append(dict(
                kind="engine_traffic", mode="training",
                start_s=float(enter - PERF_ORIGIN),
                end_s=float(max(left, r[COL["t_complete"]]) - PERF_ORIGIN),
                step=int(r[COL["step"]]), built=int(r[COL["built"]])))
    return out


def snapshot(with_rows: bool = True) -> Dict[str, Any]:
    """The rows (with the training calls' ``engine_traffic``; left out
    with ``with_rows`` false), their seconds by kind and by program, and
    ``first_useful_s``: process age at the first token a ``serve()``
    handed out, or at the entry of the first ``train_batch`` that built
    nothing, whichever came first."""
    training = _training_traffic()
    all_rows = sorted(rows() + training, key=lambda r: r["end_s"])
    by_kind: Dict[str, Dict[str, float]] = {}
    by_program: Dict[str, Dict[str, float]] = {}
    for r in all_rows:
        k = by_kind.setdefault(r["kind"], {"n": 0, "seconds": 0.0})
        k["n"] += 1
        k["seconds"] += r["end_s"] - r["start_s"]
        if r["kind"] == "program_build":
            p = by_program.setdefault(r["program"], {
                "n": 0, "own": r["own"], "trace_s": 0.0, "lower_s": 0.0,
                "backend_s": 0.0})
            p["n"] += 1
            p["own"] = max(p["own"], r["own"])    # (registered by then)
            for part in ("trace_s", "lower_s", "backend_s"):
                p[part] += r[part]
    steady = [r["start_s"] for r in training if not r["built"]]
    useful = [s for s in (_first_useful_s, min(steady, default=None))
              if s is not None]
    snap = {"clock": "process_age_s" if _AGE is not None
            else "since_package_import_s", "now_s": now(),
            "dropped": _rows.dropped, "by_kind": by_kind,
            "by_program": by_program,
            "first_useful_s": min(useful, default=None)}
    if with_rows:
        snap["rows"] = all_rows
    return snap

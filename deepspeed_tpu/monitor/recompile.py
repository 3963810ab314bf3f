"""Recompile sentinel: count jit cache misses on the engine's compiled
step functions and flag retraces after warmup.

On TPU an unexpected XLA recompile is a silent performance killer — a
shape-drifting batch or a host-rebuilt closure turns a single compiled
program into a compile-per-step treadmill, and nothing in the training
loop says so. The sentinel wraps each jitted step function:

- every call computes the ABSTRACT SIGNATURE of the arguments (treedef +
  per-leaf shape/dtype) — pure host metadata, no device sync;
- a cache miss is detected via the jitted function's ``_cache_size()``
  (growth across the call == a compile happened), falling back to
  signature-set membership when that private API is absent;
- the first ``warmup_calls`` compiles per function are expected (cold
  start; one more for each further signature its owner declares it is
  compiled at, ``instrument(..., signatures=n)``); any later miss emits
  a structured event naming the function and the signature delta vs the
  previous call, and raises ``RecompileError`` when
  ``telemetry.fail_on_recompile`` is set.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import startup


class RecompileError(RuntimeError):
    """Raised on a post-warmup jit cache miss under fail_on_recompile."""


def _leaf_desc(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    return f"py:{type(x).__name__}"


def abstract_signature(tree: Any) -> Tuple[Any, Tuple[Tuple[str, str], ...]]:
    """(hashable key, [(path, desc)]) for an argument pytree — host-side
    metadata only, never forces device values."""
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    descs = tuple((jax.tree_util.keystr(path), _leaf_desc(leaf))
                  for path, leaf in flat)
    return (treedef, tuple(d for _, d in descs)), descs


def signature_delta(old: Tuple[Tuple[str, str], ...],
                    new: Tuple[Tuple[str, str], ...]) -> List[str]:
    """Human-readable per-path differences between two signatures."""
    if tuple(old) == tuple(new):
        # The cache missed with an unchanged abstract signature: the
        # compiler keyed on something shapes/dtypes can't see (input
        # sharding/layout/committedness, donation state). One such miss is
        # expected when the donated first output becomes the second input
        # — that's inside the default warmup; repeated ones are real.
        return ["no abstract-signature change (input sharding/layout or "
                "donation-state change)"]
    o, n = dict(old), dict(new)
    out = []
    for path in n:
        if path not in o:
            out.append(f"{path}: added {n[path]}")
        elif o[path] != n[path]:
            out.append(f"{path}: {o[path]} -> {n[path]}")
    for path in o:
        if path not in n:
            out.append(f"{path}: removed {o[path]}")
    if not out:
        out.append("tree structure changed (same leaf signatures)")
    return out


class RecompileSentinel:
    """Per-engine registry of instrumented step functions."""

    def __init__(self, warmup_calls: int = 1, fail_on_recompile: bool = False,
                 on_event: Optional[Callable[[Dict[str, Any]], None]] = None):
        self.warmup_calls = max(0, int(warmup_calls))
        self.fail_on_recompile = bool(fail_on_recompile)
        self.on_event = on_event
        self.events: List[Dict[str, Any]] = []
        self.pending_error: Optional[RecompileError] = None
        self._fns: Dict[str, Dict[str, Any]] = {}
        # Cumulative seconds of the builds the missing calls made: the
        # start-up ledger's rows of them (monitor/startup.py: trace +
        # lowering + backend) — the one source. Warmup compiles count
        # too — the goodput ledger attributes ALL compile wall, cold
        # start included.
        self.compile_wall_s = 0.0

    def raise_pending(self) -> None:
        """Raise (once) a fail_on_recompile violation recorded by the last
        call. The raise is DEFERRED out of the instrumented call itself:
        the engine's step functions donate their input state, so raising
        before the caller stores the returned state would strand the
        engine on deleted buffers — the owner pumps this right after the
        state assignment instead."""
        if self.pending_error is not None:
            err, self.pending_error = self.pending_error, None
            raise err

    @property
    def recompile_count(self) -> int:
        """Post-warmup recompiles across every instrumented function."""
        return len(self.events)

    def compile_counts(self) -> Dict[str, int]:
        return {name: st["compiles"] for name, st in self._fns.items()}

    def registered_paths(self) -> Dict[str, Tuple[Callable, Tuple, Dict]]:
        """The registry handoff: {path name: (raw jitted fn, abstract
        args, abstract kwargs)} for every instrumented function that has
        compiled at least once. The abstract signature is the one
        recorded at the LAST compile (ShapeDtypeStructs with shardings —
        they survive buffer donation), so consumers (the roofline cost
        model, the analysis/ lint auditor) can AOT re-lower each path
        host-side with zero device traffic and zero fences."""
        out: Dict[str, Tuple[Callable, Tuple, Dict]] = {}
        for name, st in self._fns.items():
            fn, ab = st.get("fn"), st.get("abstract_args")
            if fn is not None and ab is not None:
                out[name] = (fn, ab[0], ab[1])
        return out

    def instrument(self, name: str, fn: Callable,
                   signatures: int = 1) -> Callable:
        """Wrap ``fn`` (typically a jitted callable). The wrapper preserves
        call/donation semantics; the raw function stays reachable via
        ``__wrapped__`` for introspection (flops profiler, hlo audit).

        ``signatures``: how many abstract signatures the owner compiles
        ``fn`` at before it serves (a ladder of row widths): its first
        calls make them, so the warmup is that many calls longer, less
        the one it already holds. Any miss after it — any other shape —
        is a violation as ever."""
        warmup_calls = self.warmup_calls + max(1, int(signatures)) - 1
        startup.register_program(fn, name)
        st = self._fns.setdefault(
            name, {"calls": 0, "compiles": 0, "seen": set(), "descs": None,
                   "fn": fn, "abstract_args": None})
        st["fn"] = fn
        cache_size = getattr(fn, "_cache_size", None)
        if not callable(cache_size):
            cache_size = None

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            # Hot-path cost discipline: with _cache_size available, miss
            # detection is two int reads — the O(num-leaves) signature walk
            # runs ONLY on a miss (args are still in scope then). The
            # reported delta is therefore vs the previously COMPILED
            # signature, which is the question the operator is asking.
            # Only the fallback path (no _cache_size) pays the per-call
            # signature, because membership IS its miss detector.
            t_call0 = time.perf_counter()
            if cache_size is not None:
                before = cache_size()
                out = fn(*args, **kwargs)
                miss = cache_size() > before
                descs = abstract_signature((args, kwargs))[1] if miss \
                    else None
            else:
                key, descs = abstract_signature((args, kwargs))
                out = fn(*args, **kwargs)
                miss = key not in st["seen"]
                st["seen"].add(key)
            prior_calls = st["calls"]
            st["calls"] += 1
            if miss:
                # Miss-only work: the call just paid seconds of compile,
                # so looking its build up and mirroring the abstract
                # signature (ShapeDtypeStructs survive buffer donation —
                # the cost model AOT-relowers from them at report
                # boundaries) is noise on top. Only a miss that built no
                # program (the signature fallback on a function that is
                # no jitted one; a fast-path miss the lowering cache
                # served) is clocked here, as the call's wall.
                dt = startup.build_seconds(
                    name, t_call0 - startup.PERF_ORIGIN)
                if dt is None:
                    dt = time.perf_counter() - t_call0
                self.compile_wall_s += dt
                from .cost_model import abstract_args_of
                st["abstract_args"] = abstract_args_of(args, kwargs)
                prev_descs, st["descs"] = st["descs"], descs
                st["compiles"] += 1
                if prior_calls >= warmup_calls:
                    self._violation(name, st, prev_descs, descs)
            return out

        wrapped.__wrapped__ = fn
        return wrapped

    def _violation(self, name: str, st: Dict[str, Any], prev_descs,
                   descs) -> None:
        delta = signature_delta(prev_descs or (), descs)
        event = {
            "fn": name,
            "call_index": st["calls"] - 1,
            "total_compiles": st["compiles"],
            "signature_delta": delta,
        }
        self.events.append(event)
        if self.on_event is not None:
            self.on_event(dict(event))
        if self.fail_on_recompile:
            self.pending_error = RecompileError(
                f"telemetry.fail_on_recompile: jit cache miss on '{name}' "
                f"after warmup (compile #{st['compiles']} at call "
                f"{st['calls'] - 1}); signature delta: "
                + "; ".join(delta))

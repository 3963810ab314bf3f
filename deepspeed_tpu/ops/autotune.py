"""Pallas block-size autotuner: one resolver for every tile decision.

Every Pallas kernel in the tree (``fused_elementwise``, ``fused_update``,
``flash_attention``/``sparse_flash``, ``grouped_gemm``) used to pick its
tiles from a scattered set of static heuristics — a fixed VMEM budget
loop here, a hand-set ``_BLOCK_TARGET`` there — though no one value
wins across shapes.  This module
replaces those call-site constants with ONE resolver:

    tile = autotune.resolve(kernel, shape, dtype, heuristic,
                            candidates, measure)

Semantics (the determinism contract, in priority order):

1. ``DS_AUTOTUNE=0`` — the resolver returns ``heuristic`` unconditionally:
   bit-for-bit today's tiles, no registry read, no search.
2. CPU / interpret mode never searches: ``search_allowed()`` is False off
   TPU, call sites pass ``measure=None``, and ``resolve`` returns the
   heuristic — tier-1 stays deterministic on any machine regardless of
   what a TPU session recorded (``DS_AUTOTUNE_FORCE=1`` is the explicit
   test/tooling escape hatch).
3. On TPU, the first resolve of a new (kernel, abstract shape, dtype,
   chip-kind) key OUTSIDE any trace times the candidate grid ONCE —
   powers of two bounded by the same VMEM budget math the heuristics
   used — and records the winner; every later resolve of that key (this
   process or the next, traced or not) hits the registry with zero
   search.
4. Under a trace (``jit``/``shard_map``/``grad`` of the training or
   serving step) nothing is ever timed: a runner's arrays are tracers
   there and ``block_until_ready`` returns at once, so the clock would
   read tracing, not the device.  The registry answers, else the
   heuristic.  A search therefore happens where a kernel entry point is
   called eagerly on concrete arrays (``chip_smoke.py``'s kernel phase).

The registry is keyed like the recompile sentinel's abstract signatures
(``kernel|dtype[dims]|chip``, host metadata only — never tracers) and
written like the async checkpoint's commit: process 0 only, tmp file +
``os.replace`` so a preempted writer can never leave a torn file.  A
corrupt registry (killed mid-copy, hand-edited) degrades to empty with a
warning — the heuristic still stands underneath.  The registry sits
beside the compile cache (``utils.compile_cache.cache_dir()``:
``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``); path
override: ``DS_AUTOTUNE_REGISTRY``.

Tiles move the SCHEDULE, not the arithmetic: every kernel computes the
same per-row/per-block fp32 expressions under any tile choice, so an
autotuned tile is bit-identical to the heuristic tile (asserted in
``tests/test_autotune.py``) — which is what makes an on-disk cache safe
to share across runs at all.
"""
from __future__ import annotations

import json
import math
import os
import tempfile
import time
import warnings
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..utils.compile_cache import cache_dir

_ENV_KNOB = "DS_AUTOTUNE"
_ENV_PATH = "DS_AUTOTUNE_REGISTRY"
_ENV_FORCE = "DS_AUTOTUNE_FORCE"

# How an entry's timings were taken; an entry recorded any other way
# (the pre-PR-21 eager clock read host re-tracing, not the device) is
# ignored and searched again.
_METHOD = "jit-best-of-3"

# Observability for tests/tooling: how many resolves searched, hit the
# registry, or fell back to the heuristic since import (or reset()).
counters: Dict[str, int] = {"search": 0, "hit": 0, "heuristic": 0}

# In-memory registry cache: path -> {key: entry}. Loaded once per path;
# invalidate() drops it (tests point DS_AUTOTUNE_REGISTRY at tmp files).
_CACHE: Dict[str, Dict[str, Any]] = {}


def enabled() -> bool:
    """DS_AUTOTUNE=0 disables everything: heuristics bit-for-bit."""
    return os.environ.get(_ENV_KNOB, "1") != "0"


def search_allowed() -> bool:
    """True when this process may time candidates: TPU backend only
    (interpret-mode timings measure the interpreter, and tier-1 must be
    deterministic). DS_AUTOTUNE_FORCE=1 is the test/tooling override."""
    if not enabled():
        return False
    if os.environ.get(_ENV_FORCE) == "1":
        return True
    return jax.default_backend() == "tpu"


def chip_kind() -> str:
    """Registry key component: the accelerator generation (tiles tuned
    on v5e are not evidence about v4), ``cpu`` off-TPU."""
    try:
        dev = jax.devices()[0]
        if dev.platform == "tpu":
            return str(dev.device_kind).replace(" ", "_")
    except Exception:  # pragma: no cover - no backend at all
        pass
    return "cpu"


def registry_path() -> str:
    env = os.environ.get(_ENV_PATH)
    if env:
        return env
    return os.path.join(cache_dir(), "autotune.json")


def _under_trace() -> bool:
    """True while a jit/shard_map/grad trace is open: a fresh array
    created here is then a tracer, and so would a runner's be."""
    return isinstance(jnp.zeros(()), jax.core.Tracer)


def reset() -> None:
    """Drop the in-memory registry cache and zero the counters (tests)."""
    _CACHE.clear()
    for k in counters:
        counters[k] = 0


def _key(kernel: str, shape: Sequence[int], dtype: Any) -> str:
    """``kernel|dtype[d0,d1,...]|chip`` — the recompile sentinel's
    per-leaf descriptor idiom (monitor/recompile.abstract_signature)."""
    dims = ",".join(str(int(d)) for d in shape)
    return f"{kernel}|{dtype}[{dims}]|{chip_kind()}"


def _load(path: str) -> Dict[str, Any]:
    if path in _CACHE:
        return _CACHE[path]
    reg: Dict[str, Any] = {}
    try:
        with open(path) as f:
            loaded = json.load(f)
        if isinstance(loaded, dict):
            reg = loaded
        else:
            raise ValueError(f"registry root is {type(loaded).__name__}")
    except FileNotFoundError:
        pass
    except Exception as e:  # corrupt file: degrade to empty, keep going
        warnings.warn(f"autotune registry {path} unreadable ({e}); "
                      f"starting empty — heuristics still apply")
    _CACHE[path] = reg
    return reg


def _write(path: str, reg: Dict[str, Any]) -> None:
    """Atomic, process-0-only: tmp in the same directory + os.replace
    (the async_ckpt/op_builder commit idiom)."""
    try:
        if jax.process_index() != 0:
            return
    except Exception:  # pragma: no cover
        pass
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   prefix=".autotune_", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(reg, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError as e:  # read-only FS etc.: in-memory cache still wins
        warnings.warn(f"autotune registry {path} not writable ({e}); "
                      f"keeping the winner in memory only")


def _encode(tile: Any) -> Any:
    if isinstance(tile, tuple):
        return [int(t) for t in tile]
    return int(tile)


def _decode(raw: Any, like: Any) -> Any:
    """Registry JSON -> the call site's tile type (int or int tuple)."""
    if isinstance(like, tuple):
        if not isinstance(raw, (list, tuple)) or len(raw) != len(like):
            return None
        return tuple(int(v) for v in raw)
    if isinstance(raw, (list, tuple)):
        return None
    try:
        return int(raw)
    except (TypeError, ValueError):
        return None


def resolve(kernel: str, shape: Sequence[int], dtype: Any, heuristic,
            candidates: Optional[Sequence] = None,
            measure: Optional[Callable[[Any], float]] = None):
    """Resolve one tile decision.

    ``heuristic`` is today's static choice (int row block or tile tuple)
    and is ALWAYS the answer when autotuning is off, search is not
    allowed here (CPU/interpret), or no usable registry entry exists and
    no ``measure`` was provided.  ``candidates`` is the legal grid the
    call site's VMEM budget math admits (the heuristic is appended if
    missing).  ``measure(tile) -> seconds`` times one candidate; a
    candidate that raises is discarded, and a search in which EVERY
    candidate raised is an error (the kernel cannot run here at all —
    the heuristic would only fail later, with less to go on).  The
    winner is recorded in the on-disk registry so the search runs once
    per (kernel, shape, dtype, chip) key — across processes.  Under a
    trace no search runs (see the module docstring).
    """
    if not search_allowed():
        counters["heuristic"] += 1
        return heuristic
    cands = [c for c in (candidates or ())]
    if heuristic not in cands:
        cands.append(heuristic)
    key = _key(kernel, shape, dtype)
    path = registry_path()
    reg = _load(path)
    ent = reg.get(key)
    if isinstance(ent, dict) and ent.get("method") == _METHOD:
        tile = _decode(ent.get("tile"), heuristic)
        if tile is not None and tile in cands:
            counters["hit"] += 1
            return tile
        # Entry exists but is outside today's legal grid (budget math or
        # candidate set changed since it was recorded): ignore it.
    if measure is None or len(cands) < 2 or _under_trace():
        counters["heuristic"] += 1
        return heuristic
    counters["search"] += 1
    timings: Dict[Any, float] = {}
    errors: Dict[Any, str] = {}
    for c in cands:
        try:
            t = float(measure(c))
        except Exception as e:  # fails to compile/run: not a winner
            errors[c] = f"{type(e).__name__}: {e}"
            continue
        if math.isfinite(t):
            timings[c] = t
    if not timings:
        raise RuntimeError(
            f"autotune search for {key}: every candidate failed — "
            + "; ".join(f"{c}: {m[:200]}" for c, m in errors.items()))
    best = min(timings, key=lambda c: timings[c])
    t_h = timings.get(heuristic)
    ent = {
        "tile": _encode(best),
        "heuristic": _encode(heuristic),
        "timings_s": {str(c): round(timings[c], 9) for c in timings},
        "speedup_vs_heuristic":
            round(t_h / timings[best], 4) if t_h else None,
        "recorded_unix": int(time.time()),
        "method": _METHOD,
    }
    reg[key] = ent
    _write(path, reg)
    return best


def measure_from_runner(runner: Callable[[Any], Any],
                        repeats: int = 3) -> Callable[[Any], float]:
    """Wrap ``runner(tile) -> jax value(s)`` into a wall-clock measure:
    the runner is JITTED per tile — an eager ``pallas_call`` re-traces
    and re-lowers its kernel on every call, ~0.1 s of host work that
    buried the device time (first chip run, PR 21: a 4x larger GELU
    timed the same) — then one warm-up call (compile) and
    best-of-``repeats`` with ``block_until_ready`` fencing both sides.
    The runner must hand back concrete arrays (``resolve`` never
    searches under a trace)."""
    def measure(tile) -> float:
        fn = jax.jit(lambda: runner(tile))
        out = jax.block_until_ready(fn())  # compile + warm
        if any(isinstance(a, jax.core.Tracer)
               for a in jax.tree_util.tree_leaves(out)):
            raise RuntimeError("autotune runner returned tracers: the "
                               "clock would read tracing, not the device")
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best
    return measure


def pow2_candidates(lo: int, hi: int,
                    fits: Optional[Callable[[int], bool]] = None
                    ) -> Tuple[int, ...]:
    """Powers of two in [lo, hi] passing the call site's VMEM-budget
    predicate — the shared candidate-grid constructor."""
    out = []
    c = 1 << max(0, (lo - 1).bit_length())
    while c <= hi:
        if c >= lo and (fits is None or fits(c)):
            out.append(c)
        c *= 2
    return tuple(out)


__all__ = ["resolve", "measure_from_runner", "pow2_candidates",
           "enabled", "search_allowed", "chip_kind", "registry_path",
           "reset", "counters"]

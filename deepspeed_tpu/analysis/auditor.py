"""The compile-time program auditor: run the lint pipeline over every
compiled step function an engine owns.

Input comes from the recompile sentinel's registry (monitor/recompile.py
records each instrumented function and the abstract signature of its
last compile — ``RecompileSentinel.registered_paths()``), so the audit
re-lowers host-side from metadata that survives buffer donation: zero
device traffic, zero fences. A standalone entry point (``lint_jit``)
audits any jitted callable the same way for tests and tools.

Per path the auditor builds ONE ``LintContext`` — the traced jaxpr (with
the jit-level donation declaration read off the pjit eqn), the
optimized-HLO text, and an ``hlo_audit.CommAudit`` over it — then runs
the pass pipeline (analysis/passes.py). A pass crashing degrades to a
structured error on that path's result, never to a dead audit.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .findings import (LintConfig, LintContext, LintFinding, LintReport,
                       PathResult, Waiver, apply_waivers)
from .passes import PASSES


def _trace_program(fn: Callable, args: Tuple, kwargs: Dict
                   ) -> Tuple[Any, Tuple[bool, ...], Tuple[Any, ...]]:
    """(body ClosedJaxpr, donated_invars, flat in_avals) of one program.

    Tracing the JITTED callable yields an outer jaxpr with a single jit
    eqn whose params carry the donation declaration — the jit-level truth
    the donation pass diffs against the compiled alias table. A plain
    callable (no jit eqn) traces with an empty donation vector.
    """
    import jax
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    outer = closed.jaxpr
    in_avals = tuple(v.aval for v in outer.invars)
    if len(outer.eqns) == 1 and outer.eqns[0].primitive.name == "jit" \
            and len(outer.eqns[0].invars) == len(outer.invars):
        eqn = outer.eqns[0]
        donated = tuple(eqn.params.get("donated_invars") or
                        (False,) * len(in_avals))
        return eqn.params["jaxpr"], donated, in_avals
    return closed, (False,) * len(in_avals), in_avals


def build_context(name: str, fn: Callable, abstract_args: Tuple,
                  abstract_kwargs: Dict, meta: Optional[Dict[str, Any]],
                  config: Optional[LintConfig] = None) -> LintContext:
    """Lower + compile (AOT, host-side) and trace one program into the
    context the passes consume."""
    import jax
    from ..parallel import hlo_audit
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    compiled = fn.lower(*abstract_args, **abstract_kwargs).compile()
    hlo = compiled.as_text()
    # Which flat inputs survived as entry parameters (keep_unused=False
    # drops unused args): the donation pass needs it to map alias-table
    # parameter numbers back onto the declared donated_invars. Private
    # API with a graceful None fallback — the _cache_size precedent.
    kept = None
    try:
        kv = getattr(getattr(compiled, "_executable", None),
                     "_kept_var_idx", None)
        if kv is not None:
            kept = tuple(sorted(int(i) for i in kv))
    except Exception:
        kept = None
    jaxpr, donated, in_avals = _trace_program(fn, abstract_args,
                                              abstract_kwargs)
    return LintContext(
        name=name, jaxpr=jaxpr, donated_invars=donated, in_avals=in_avals,
        hlo_text=hlo, audit=hlo_audit.audit_text(hlo), kept_var_idx=kept,
        meta=dict(meta or {}), config=config or LintConfig())


def lint_path(name: str, fn: Callable, abstract_args: Tuple,
              abstract_kwargs: Dict,
              meta: Optional[Dict[str, Any]] = None,
              config: Optional[LintConfig] = None,
              passes: Optional[Sequence[str]] = None) -> PathResult:
    """Audit ONE compiled program; per-pass failures become structured
    errors, not exceptions."""
    result = PathResult(name=name)
    try:
        ctx = build_context(name, fn, abstract_args, abstract_kwargs,
                            meta, config)
    except Exception as e:      # lowering failed — report, don't die
        result.errors.append(
            f"{name}: context build failed: {type(e).__name__}: "
            f"{str(e)[:300]}")
        return result
    for pname in (passes or PASSES):
        run = PASSES.get(pname)
        if run is None:
            result.errors.append(f"{name}: unknown lint pass {pname!r}")
            continue
        try:
            result.findings.extend(run(ctx))
        except Exception as e:
            result.errors.append(
                f"{name}/{pname}: {type(e).__name__}: {str(e)[:300]}")
    return result


def lint_jit(fn: Callable, *args, name: str = "program",
             meta: Optional[Dict[str, Any]] = None,
             config: Optional[LintConfig] = None,
             passes: Optional[Sequence[str]] = None,
             **kwargs) -> PathResult:
    """Standalone entry: audit any (jitted or plain) callable on concrete
    or ShapeDtypeStruct args. Compile-only; nothing executes."""
    return lint_path(name, fn, args, kwargs, meta=meta, config=config,
                     passes=passes)


def lint_sentinel(sentinel, meta_by_path: Optional[Dict[str, Dict]] = None,
                  config: Optional[LintConfig] = None,
                  waivers: Optional[Sequence[Waiver]] = None,
                  passes: Optional[Sequence[str]] = None) -> LintReport:
    """Audit every path the recompile sentinel has recorded (the PR-5
    ``fn``/``abstract_args`` registry handoff). ``meta_by_path`` supplies
    the engine-truth each pass needs (grad-sync mode, declared state
    bytes, ...); paths without an entry run with empty meta."""
    config = config or LintConfig()
    meta_by_path = meta_by_path or {}
    results: List[PathResult] = []
    for name, (fn, a_args, a_kwargs) in sentinel.registered_paths().items():
        results.append(lint_path(name, fn, a_args, a_kwargs,
                                 meta=meta_by_path.get(name),
                                 config=config, passes=passes))
    findings = [f for r in results for f in r.findings]
    unwaived, waived, stale = apply_waivers(findings, waivers or [])
    return LintReport(paths=results, unwaived=unwaived, waived=waived,
                      stale_waivers=stale, config=config)


def lint_engine(engine, config: Optional[LintConfig] = None,
                waivers: Optional[Sequence[Waiver]] = None,
                passes: Optional[Sequence[str]] = None) -> LintReport:
    """Audit every compiled path a DeepSpeedEngine has run, with the
    engine's own declarations as pass metadata. Requires telemetry (the
    sentinel IS the registry); raises otherwise so a disabled-telemetry
    run can't silently audit nothing."""
    sentinel = getattr(engine.telemetry, "sentinel", None)
    if sentinel is None:
        raise ValueError(
            "lint_engine needs the recompile sentinel's registry — enable "
            "the telemetry block (telemetry.enabled: true) so compiled "
            "paths are recorded")
    meta = {name: engine._lint_path_meta(name)
            for name in sentinel.registered_paths()}
    return lint_sentinel(sentinel, meta_by_path=meta, config=config,
                         waivers=waivers, passes=passes)


__all__ = ["build_context", "lint_path", "lint_jit", "lint_sentinel",
           "lint_engine"]

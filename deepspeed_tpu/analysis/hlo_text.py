"""Shared HLO-text parsing for the static-analysis layer.

One home for the mechanics every compiled-program pass needs — splitting
optimized-HLO text into computations, walking instructions, sizing
(possibly tuple) shapes, attributing computations to ``while`` loops
(``lax.scan`` bodies), and reading the module header's input/output alias
table. ``parallel/hlo_audit.py`` (the original collective auditor) and
``analysis/passes.py`` (the lint suite) both parse compiled programs; the
primitives live here so the two stay byte-for-byte consistent.

Everything operates on ``jit(...).lower(...).compile().as_text()`` output
— pure host-side string work, no jax import, no device traffic.
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

# Bytes per element for the HLO primitive types that can appear in
# instruction shapes. (f8 variants share one entry per byte width.)
DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# `%name = <shape> <opcode>(<operands>), attr=..., ...` — async ops
# appear as `<opcode>-start`; the matching `-done` carries no new buffer.
# Tuple shapes allow one nesting level (async variadic collectives wrap
# the operand/result tuples in an outer pair) but NOT `[^=]*`: XLA
# annotates long tuples with `/*index=N*/` comments whose `=` would kill
# that match (the 8-way all-to-all result tuple is the canonical victim).
INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\((?:[^()]|\([^()]*\))*\)"
    r"|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>[a-z\-]+(?:-start)?)\(")
SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
BODY_RE = re.compile(r"body=%([\w.\-]+)")
CALLEE_RE = re.compile(
    r"(?:calls|to_apply|condition|body|branch_computations)="
    r"(?:\{)?%([\w.\-]+(?:,\s*%[\w.\-]+)*)")
OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CONST_RE = re.compile(r"constant\((\d+)\)")
_COND_RE = re.compile(r"condition=%([\w.\-]+)")
# Module-header alias table: `input_output_alias={ {1}: (0, {}, may-alias),
# {0,2}: (3, {}, must-alias) }` — output tuple index -> (param number,
# param index, kind). Braces nest, so the block is cut by scanning, not
# by regex.
_ALIAS_ENTRY_RE = re.compile(
    r"\{(?P<out>[\d,\s]*)\}:\s*\((?P<param>\d+),\s*\{(?P<pidx>[\d,\s]*)\}")
_ENTRY_LAYOUT_RE = re.compile(
    r"entry_computation_layout=\{\((?P<params>.*?)\)->")


def _header_attr_block(hlo_text: str, attr: str) -> Optional[str]:
    """The brace-balanced `{...}` value of a module-header attribute."""
    marker = f"{attr}={{"
    start = hlo_text.find(marker)
    if start < 0:
        return None
    i = start + len(marker)
    depth = 1
    while i < len(hlo_text) and depth:
        if hlo_text[i] == "{":
            depth += 1
        elif hlo_text[i] == "}":
            depth -= 1
        i += 1
    return hlo_text[start + len(marker):i - 1]


def parse_shape_bytes(shape_str: str, largest_only: bool = False
                      ) -> Tuple[int, List[str]]:
    """Total bytes + the individual `dtype[dims]` strings of a (possibly
    tuple) HLO shape. Layout annotations (`{1,0}`) are ignored.

    ``largest_only``: return the LARGEST component's bytes instead of the
    sum — for async ``-start`` results (whose tuple aliases the input
    buffer alongside the output, plus u32 context scalars) and for sizing
    "what is the biggest buffer this instruction materializes".
    """
    shapes, total, largest = [], 0, 0
    for dtype, dims in SHAPE_RE.findall(shape_str):
        if dtype not in DTYPE_BYTES:
            continue    # token types (after-all etc.) carry no payload
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes = n * DTYPE_BYTES[dtype]
        total += nbytes
        largest = max(largest, nbytes)
        shapes.append(f"{dtype}[{dims}]")
    return (largest if largest_only else total), shapes


def split_computations(hlo_text: str) -> Dict[str, List[str]]:
    """{computation name: its instruction lines}. Header lines are
    `%name (params) -> result {`; instruction lines always contain an
    ` = ` assignment (a bare `=` check would misfire on the `/*index=N*/`
    markers in long tuple params)."""
    comp_lines: Dict[str, List[str]] = {}
    computation = ""
    for line in hlo_text.splitlines():
        comp = COMP_RE.match(line)
        if comp and " = " not in line:
            computation = comp.group(1)
            comp_lines.setdefault(computation, [])
            continue
        comp_lines.setdefault(computation, []).append(line)
    return comp_lines


def loop_computations(comp_lines: Dict[str, List[str]]) -> set:
    """Computation names reachable from any ``while`` body — collectives
    (or any op) there run once per trip, not once per step. Follows
    calls/branches transitively so an op inside a ``lax.cond`` inside a
    scan is still loop-tagged."""
    callees: Dict[str, set] = {}
    roots: set = set()
    for name, lines in comp_lines.items():
        refs: set = set()
        for line in lines:
            for mm in CALLEE_RE.finditer(line):
                for ref in mm.group(1).split(","):
                    refs.add(ref.strip().lstrip("%"))
            bm = BODY_RE.search(line)
            if bm and " while(" in line:
                roots.add(bm.group(1))
        callees[name] = refs
    reach, frontier = set(), set(roots)
    while frontier:
        c = frontier.pop()
        if c in reach:
            continue
        reach.add(c)
        frontier |= callees.get(c, set())
    return reach


class Instruction(NamedTuple):
    """One parsed HLO instruction, positioned in its computation."""
    computation: str
    name: str
    opcode: str          # raw (may carry a -start suffix)
    shape_str: str
    rest: str            # the line from the opening call paren onward
    in_loop: bool
    op_name: str         # jax op metadata (attribution), "" if absent


def iter_instructions(hlo_text: str) -> Iterator[Instruction]:
    """Walk every instruction of every computation with loop attribution
    — the shared traversal the lint passes build on."""
    comp_lines = split_computations(hlo_text)
    loops = loop_computations(comp_lines)
    for computation, lines in comp_lines.items():
        in_loop = computation in loops
        for line in lines:
            m = INSTR_RE.match(line)
            if not m:
                continue
            rest = line[m.end():]
            om = OPNAME_RE.search(rest)
            yield Instruction(computation, m.group("name"), m.group("op"),
                              m.group("shape"), rest, in_loop,
                              om.group(1) if om else "")


def ops_in_units_of(hlo_text: str, unit: int) -> List[Tuple[str, int]]:
    """(opcode, elements) of every instruction whose largest output array
    holds a whole number (>= 1) of ``unit`` elements — e.g. ``unit`` = one
    layer of a KV pool finds whatever handles the pool, or a layer of it,
    as a whole. In a program that updates the pool in place these are
    parameters, tuples, the layer ``while``, bitcasts and the aliased
    kernels' custom calls (tests/test_tpu_compile.py,
    tools/compile_rehearsal.py)."""
    found = []
    for ins in iter_instructions(hlo_text):
        n = 0
        for _, dims in SHAPE_RE.findall(ins.shape_str):
            size = 1
            for d in dims.split(","):
                size *= int(d) if d else 1
            n = max(n, size)
        if n >= unit and n % unit == 0:
            found.append((ins.opcode, n))
    return found


def while_trip_counts(hlo_text: str) -> List[int]:
    """Best-effort static trip counts: the integer constants appearing in
    each ``while`` instruction's CONDITION computation (a ``lax.scan``'s
    bound compiles to ``compare(i, constant(T)), direction=LT``). Returns
    every candidate, largest first — callers check membership of the
    analytic count rather than assuming a unique bound."""
    comp_lines = split_computations(hlo_text)
    conds: List[str] = []
    for lines in comp_lines.values():
        for line in lines:
            if " while(" in line:
                cm = _COND_RE.search(line)
                if cm:
                    conds.append(cm.group(1))
    counts: List[int] = []
    for cond in conds:
        for line in comp_lines.get(cond, []):
            counts.extend(int(c) for c in _CONST_RE.findall(line))
    return sorted(set(counts), reverse=True)


def input_output_alias_params(hlo_text: str) -> List[int]:
    """Parameter numbers the compiled module aliases to outputs (the
    header's ``input_output_alias`` table). Donated inputs jax could pair
    with a matching output appear here; a declared donation MISSING from
    this list kept its buffer live across the call — the memory the
    donation promised back was never returned."""
    block = _header_attr_block(hlo_text, "input_output_alias")
    if block is None:
        return []
    return [int(e.group("param"))
            for e in _ALIAS_ENTRY_RE.finditer(block)]


def entry_parameter_shapes(hlo_text: str) -> List[str]:
    """The entry computation's parameter shape strings (per-device, post
    partitioning), in parameter-number order — from the module header's
    ``entry_computation_layout``."""
    m = _ENTRY_LAYOUT_RE.search(hlo_text)
    if not m:
        return []
    text = m.group("params")
    shapes: List[str] = []
    for sm in SHAPE_RE.finditer(text):
        shapes.append(f"{sm.group(1)}[{sm.group(2)}]")
    return shapes


__all__ = [
    "DTYPE_BYTES", "INSTR_RE", "SHAPE_RE", "COMP_RE", "BODY_RE",
    "CALLEE_RE", "OPNAME_RE", "Instruction", "parse_shape_bytes",
    "split_computations", "loop_computations", "iter_instructions",
    "while_trip_counts", "input_output_alias_params",
    "entry_parameter_shapes",
]

"""Serving engine: chunk programs an admission dispatches — the traced
window's ``prefill`` spans' ``chunks`` summed over their ``slots`` summed
(a span is one admission batch: one slot a dp group at most, each chunk
program one dispatch for all of them).  1 when every prompt's new tokens
fit one program; a prompt cut at a snapshot's boundary runs one more.
``None`` for a run not traced or a window without a ``prefill`` span."""
from perfbench.lib import program_trace


def per_admit(spans):
    """``spans``: {name: [(start, duration, args)]}."""
    rows = [a for _, _, a in spans.get("prefill", [])
            if a.get("slots") and a.get("chunks") is not None]
    if not rows:
        return None
    return sum(int(a["chunks"]) for a in rows) \
        / sum(int(a["slots"]) for a in rows)


def read(record):
    tr = program_trace.current(record)
    return None if tr is None else per_admit(tr["spans"])

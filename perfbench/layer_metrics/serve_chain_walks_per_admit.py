"""KV cache: whole-prompt hash walks an admission costs the host — the
traced window's ``prefill`` spans' ``chain_walks`` summed over their
``slots`` summed (a span is one admission batch; ``chain_walks`` counts the
walks made for its requests since each was queued, every pass of
``select_slot`` that refused one of them included).  1 where a request's
chain is walked once and kept with it.  ``None`` for a run not traced, a
window without a ``prefill`` span, and a program whose spans carry no such
arg (every walk was then each question's own, uncounted)."""
from perfbench.lib import program_trace


def per_admit(spans):
    """``spans``: {name: [(start, duration, args)]}."""
    rows = [a for _, _, a in spans.get("prefill", [])
            if a.get("slots") and a.get("chain_walks") is not None]
    if not rows:
        return None
    return sum(int(a["chain_walks"]) for a in rows) \
        / sum(int(a["slots"]) for a in rows)


def read(record):
    tr = program_trace.current(record)
    return None if tr is None else per_admit(tr["spans"])

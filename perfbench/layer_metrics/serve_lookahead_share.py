"""Serving engine: percent of the traced window's ``decode`` spans whose
dispatch went out while the iteration before was still unfetched (the
spans' ``ahead``: the loop runs one iteration ahead of its token fetch, so
the fetch, the emission and the host's pass lie under the next iteration's
device time).  What stays below 100 are the first dispatch of a stretch
and the spans that only fetched.  ``None`` on a program whose ``decode``
spans carry no such arg."""
from perfbench.lib import scope_trace


def read(record):
    ahead, spans = scope_trace.span_arg_sum(record, "decode", "ahead")
    return 100.0 * ahead / spans if spans else None

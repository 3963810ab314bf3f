"""Serving engine: percent, rows the window's prefills needed
(``prefill``: ``prompt_tokens`` - ``cached_tokens``) over the rows their chunk
programs computed (``rows_computed``: ``[G, chunk]`` a dispatch).  ``None``
without the arg."""
from perfbench.lib import serve_timeline


def read(record):
    return serve_timeline.metric(record, "prefill_row_fill")

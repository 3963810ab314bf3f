"""Kernels: how many times a prefill chunk's attend reads the key rows in its
reach, over the traced window: the ``prefill`` spans'
``attend_rows_read_full`` (key rows the chunk programs' attends WALK, a run
of query rows at a time: ``inference/kv_pages.attend_rows``) over their
``context_tokens_in_reach_full`` (the rows those chunks may read, once).  1
where a chunk's query rows share one walk; ~8 where a 512-row chunk at 8
query heads a K/V head goes in runs of 64 rows that each walk the stream's
whole reach.  ``None`` where no traced span carries both (a program that does
not count the walk)."""
from perfbench.lib import scope_trace


def read(record):
    read_, n = scope_trace.span_arg_sum(record, "prefill",
                                        "attend_rows_read_full")
    reach, m = scope_trace.span_arg_sum(record, "prefill",
                                        "context_tokens_in_reach_full")
    if not n or not m or not reach:
        return None
    return read_ / reach

"""Kernels: blocks of pooled keys the selection GATHERED to score them over
the blocks in its streams' reach, in percent, from the ``decode`` spans'
counters ``ck_blocks_read`` / ``sparse_blocks_in_reach`` over the traced
window (both a sparse layer and K/V head).  A selection that gathers every
stream's pooled keys through its whole table reads the table's width a
stream whatever is live (2,072 slots for ~1,115 blocks in reach: ~186%);
one that reads a block several streams share once a tile of 32 of them
reads an eighth of the reach (11 tiles x (2,072 + 32 x 32 own slots):
~12%); 186% again says its tables shared nothing (or a stream's own tail
passed the bound) and the per-stream arm ran.  ``None`` where the program has no such
counter."""
from perfbench.lib import scope_trace


def read(record):
    read_, n = scope_trace.span_arg_sum(record, "decode", "ck_blocks_read")
    reach, _ = scope_trace.span_arg_sum(record, "decode",
                                        "sparse_blocks_in_reach")
    if not n or not reach:
        return None
    return 100.0 * read_ / reach

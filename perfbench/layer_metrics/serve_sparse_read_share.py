"""KV cache: blocks the sparse attend WALKED over the blocks in its streams'
reach (what a dense attend would walk), in percent, from the ``decode``
spans' counters ``sparse_blocks_read`` / ``sparse_blocks_in_reach`` over the
traced window: ~6% at 71k of context; 100% says the selection is not
running.  ``None`` where the program has no such counters."""
from perfbench.lib import scope_trace


def read(record):
    read_, n = scope_trace.span_arg_sum(record, "decode",
                                        "sparse_blocks_read")
    reach, _ = scope_trace.span_arg_sum(record, "decode",
                                        "sparse_blocks_in_reach")
    if not n or not reach:
        return None
    return 100.0 * read_ / reach

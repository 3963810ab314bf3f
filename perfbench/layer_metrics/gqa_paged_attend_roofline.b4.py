"""Kernels: the paged attend's share of its roofline in a BLOCK pass
(``gqa_paged_attend_roofline``'s twin: that reader takes
``record["afmoe"]`` and one query row a stream): the larger of (K and V
rows of the key rows IN REACH, once a stream for its ``block_length`` x 8
query rows a K/V head: ``lib/sdar_costs.py``) / peak bytes/s and (score +
value FLOPs for all of those rows and every query head) / peak FLOP/s, over
``_pattn_kernel``'s device time inside ``decode_step`` (all layers).  Rows
in reach are the ``decode`` spans' ``context_tokens_in_reach`` over the
traced window.  Bound by bandwidth at 32 query rows a K/V head (32 FLOPs a
byte; ridge: 240)."""
from perfbench.lib import scope_trace, sdar_costs as costs


def read(record):
    sizes = record.get("sdar")
    secs, execs = scope_trace.kernel_seconds(record, "_pattn_kernel")
    rows, n = scope_trace.span_arg_sum(record, "decode",
                                       "context_tokens_in_reach")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    return costs.roofline_share(
        costs.attend_flops(sizes, rows / n),
        costs.attend_bytes(sizes, rows / n), secs / execs, record["peaks"])

"""Kernels: the paged attend's share of its roofline in decode at SEVEN
query heads a K/V head (``gqa_paged_attend_roofline``'s twin: that reader
takes ``record["afmoe"]``): the larger of (K and V rows of the key rows IN
REACH: ``lib/smallthinker_costs.py``) / peak bytes/s and (score + value
FLOPs for every query head) / peak FLOP/s, over ``_pattn_kernel``'s device
time inside ``decode_step`` (all layers, both classes).  Rows in reach are
the ``decode`` spans' ``context_tokens_in_reach`` over the traced window:
what an iteration MAY read, whatever the kernel walks.  Bound by bandwidth
(7 FLOPs a byte; ridge: 240)."""
from perfbench.lib import scope_trace, smallthinker_costs as costs


def read(record):
    sizes = record.get("smallthinker")
    secs, execs = scope_trace.kernel_seconds(record, "_pattn_kernel")
    rows, n = scope_trace.span_arg_sum(record, "decode",
                                       "context_tokens_in_reach")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    return costs.roofline_share(
        costs.attend_flops(sizes, rows / n),
        costs.attend_bytes(sizes, rows / n), secs / execs, record["peaks"])

"""Kernels: the paged attend's share of its roofline in decode, with fewer
K/V heads than query heads and a window: the larger of (K and V rows of
the key rows IN REACH: ``lib/afmoe_costs.py``) / peak bytes/s and (score +
value FLOPs for every query head) / peak FLOP/s, over ``_pattn_kernel``'s
device time inside ``decode_step`` (all layers, both classes).  Rows in
reach are the ``decode`` spans' ``context_tokens_in_reach`` over the traced
window: what an iteration MAY read, whatever the kernel walks, so a kernel
that reads past the window reads a low share.  Bound by bandwidth at 8
query heads a K/V head (8 FLOPs a byte; ridge: 240)."""
from perfbench.lib import afmoe_costs, scope_trace


def read(record):
    sizes = record.get("afmoe")
    secs, execs = scope_trace.kernel_seconds(record, "_pattn_kernel")
    rows, n = scope_trace.span_arg_sum(record, "decode",
                                       "context_tokens_in_reach")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    per_exec = rows / n
    return afmoe_costs.roofline_share(
        afmoe_costs.attend_flops(sizes, per_exec),
        afmoe_costs.attend_bytes(sizes, per_exec),
        secs / execs, record["peaks"])

"""Kernels: the state-update kernel's share of its roofline in decode: the
larger of (every live stream's state of every layer read once and written
once: 8 K/V heads x 8,256 pair products x 129 float32) / peak bytes/s and
(update + the query heads' reads) / peak FLOP/s, over
``_state_update_kernel``'s device time inside ``decode_step``.  Live
streams are the ``decode`` spans' ``state_pages_live`` over the traced
window.  Bound by bandwidth (0.75 FLOP a byte)."""
from perfbench.lib import retention_costs, scope_trace


def read(record):
    sizes = record.get("retention")
    secs, execs = scope_trace.kernel_seconds(record, "_state_update_kernel")
    live, n = scope_trace.span_arg_sum(record, "decode", "state_pages_live")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    per_exec = live / n                  # live streams a decode execution
    return retention_costs.roofline_share(
        retention_costs.state_update_flops(sizes, per_exec),
        retention_costs.state_update_bytes(sizes, per_exec),
        secs / execs, record["peaks"])

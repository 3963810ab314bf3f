"""Kernels: the delta-rule decode kernel's share of its roofline: the larger
of (every live stream's state of every KDA layer read once and written once:
32 heads x 128 x 128 float32) / peak bytes/s and (7 operations a state
entry) / peak FLOP/s, over ``_kda_state_update_kernel``'s device time inside
``decode_step``.  Live streams are the ``decode`` spans' ``state_pages_live``
over the traced window.  Bound by bandwidth (0.9 FLOP a byte).  ``None``
where the program has no such kernel."""
from perfbench.lib import kda_costs, scope_trace


def read(record):
    sizes = record.get("kda")
    secs, execs = scope_trace.kernel_seconds(record,
                                             "_kda_state_update_kernel")
    live, n = scope_trace.span_arg_sum(record, "decode", "state_pages_live")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    per_exec = live / n                  # live streams a decode execution
    return kda_costs.roofline_share(
        kda_costs.state_update_flops(sizes, per_exec),
        kda_costs.state_update_bytes(sizes, per_exec),
        secs / execs, record["peaks"])

"""Kernels: the Lightning decode kernel's share of its roofline: the larger
of (every live stream's state of every Lightning layer read once and written
once: 32 heads x 128 x 128 float32) / peak bytes/s and (5 operations a state
entry) / peak FLOP/s, over ``_ssm_state_update_kernel``'s device time inside
``decode_step`` (every head a group of its own: several one-head groups a
grid step).  Live streams are the ``decode`` spans' ``state_pages_live``.
Bound by bandwidth (0.6 FLOP a byte).  ``None`` where the program has no
such kernel."""
from perfbench.lib import minicpm_sala_costs as costs, scope_trace


def read(record):
    sizes = record.get("sala")
    secs, execs = scope_trace.kernel_seconds(record,
                                             "_ssm_state_update_kernel")
    live, n = scope_trace.span_arg_sum(record, "decode", "state_pages_live")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    return costs.roofline_share(
        costs.state_update_flops(sizes, live / n),
        costs.state_update_bytes(sizes, live / n), secs / execs,
        record["peaks"])

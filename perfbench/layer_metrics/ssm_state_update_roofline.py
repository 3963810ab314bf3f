"""Kernels: the state-space decode kernel's share of its roofline: the
larger of (every live stream's state of every layer read once and written
once: 32 heads x 256 x 128 float32) / peak bytes/s and (5 operations a state
entry) / peak FLOP/s, over ``_ssm_state_update_kernel``'s device time inside
``decode_step``.  Live streams are the ``decode`` spans'
``state_pages_live`` over the traced window.  Bound by bandwidth (0.6 FLOP a
byte).  ``None`` where the program has no such kernel."""
from perfbench.lib import scope_trace, ssm_costs


def read(record):
    sizes = record.get("ssm")
    secs, execs = scope_trace.kernel_seconds(record,
                                             "_ssm_state_update_kernel")
    live, n = scope_trace.span_arg_sum(record, "decode", "state_pages_live")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    per_exec = live / n                  # live streams a decode execution
    return ssm_costs.roofline_share(
        ssm_costs.state_update_flops(sizes, per_exec),
        ssm_costs.state_update_bytes(sizes, per_exec),
        secs / execs, record["peaks"])

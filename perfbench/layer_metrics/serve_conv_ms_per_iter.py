"""Kernels: device self time under the ``conv`` named scope (a gated
short-convolution layer: ``conv_in_proj``, ``conv_mix`` — the gates'
product, the filter of ``conv_L_cache`` taps and the rewrite of the
stream's page — and ``conv_out_proj``) in executions of the ``decode_step``
program, per WHOLE execution.  ``None`` where the trace holds no such scope
(a model without conv layers)."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="conv")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

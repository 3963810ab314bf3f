"""Kernels: device self time under the ``attn`` > ``attend_full`` named
scope (the full-attention layers' paged attend over a stream's whole
context) inside executions of the ``decode_step`` program, per WHOLE
execution.  ``None`` where the trace holds no such scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="attend_full")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

"""Kernels: device self time under the ``attn`` > ``state_update`` named
scope (the retention's decode kernel and the small operands made for it)
inside executions of the ``decode_step`` program, per WHOLE execution.
``None`` where the trace holds no such scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="state_update")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

"""Kernels: device self time under the ``attn`` > ``attend_sparse`` named
scope (the attend over the chosen blocks only: the per-K/V-head plan and
``_pattn_kernel`` under it) in executions of the ``decode_step`` program,
per WHOLE execution.  ``None`` where the trace holds no such scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="attend_sparse")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

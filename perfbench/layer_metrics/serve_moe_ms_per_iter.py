"""MoE: device self time under the ``moe`` named scope (router, dispatch,
the held experts' grouped product, combine, shared expert) inside
executions of the ``decode_step`` program, per WHOLE execution.
``None`` where the trace holds no such scope."""
from perfbench.lib import scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = scope_trace.seconds(record, program="decode_step", scope="moe")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

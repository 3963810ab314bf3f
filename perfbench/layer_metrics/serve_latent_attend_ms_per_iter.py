"""Kernels: device self time under the ``attn`` > ``attend`` named scope
(the latent attend's kernel and its once-an-execution plan) inside
executions of the ``decode_step`` program, per WHOLE execution.
``None`` where the trace holds no such scope."""
from perfbench.lib import scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = scope_trace.seconds(record, program="decode_step", scope="attend")
    if not execs or not secs or not (record.get("latent") or {}):
        return None
    return 1e3 * secs / execs

"""Kernels: device self time under the ``attn`` > ``select`` named scope (the
block selection of the sparse layers: the pooled keys gathered through the
table, their scores, softmax, sums, maxima and top-k; plain ``jax.numpy``)
in executions of the ``decode_step`` program, per WHOLE execution.  ``None``
where the trace holds no such scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="select")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

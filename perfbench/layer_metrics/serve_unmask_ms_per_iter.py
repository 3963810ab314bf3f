"""Kernels: device self time under the ``unmask`` named scope (the
proposals over the whole vocabulary, their confidences in fp32 and the
rule's choice, for every slot's block) inside executions of the
``decode_step`` program, per WHOLE execution.  ``None`` where the trace
holds no such scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="unmask")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

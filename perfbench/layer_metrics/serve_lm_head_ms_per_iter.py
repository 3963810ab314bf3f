"""Kernels: device self time under the ``lm_head`` and ``sample`` named
scopes (the streams' sum, the final norm, the head's product over the WHOLE
vocabulary, and the choice of a token from ``[streams, vocabulary]``
logits) in executions of the ``decode_step`` program, per WHOLE execution.
``None`` where the trace holds neither scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = sum(retention_trace.seconds(record, program="decode_step",
                                       scope=scope)
               for scope in ("lm_head", "sample"))
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

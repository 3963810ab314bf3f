"""Kernels: device self time under the Lightning layers' named scopes
(``la_proj``, ``la_state_update`` — the decode kernel over every live
stream's page — ``la_gate_norm`` and ``la_out``) in executions of the
``decode_step`` program, per WHOLE execution.  ``None`` where the trace holds
no such scope."""
from perfbench.lib import retention_trace, scope_trace

SCOPES = ("la_proj", "la_state_update", "la_gate_norm", "la_out")


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = sum(retention_trace.seconds(record, program="decode_step",
                                       scope=name) for name in SCOPES)
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

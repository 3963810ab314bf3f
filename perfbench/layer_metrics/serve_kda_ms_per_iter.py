"""Kernels: device self time under the Kimi-Delta-Attention mixer's named
scopes (``attn`` > ``kda_proj``, ``kda_conv``, ``kda_gate``, ``kda_update``
— the decode kernel over every live stream's page — and ``kda_out``) in
executions of the ``decode_step`` program, per WHOLE execution.  The scopes
do not nest in one another, so their seconds add.  ``None`` where the trace
holds no such scope (a model without KDA layers)."""
from perfbench.lib import retention_trace, scope_trace

SCOPES = ("kda_proj", "kda_conv", "kda_gate", "kda_update", "kda_out")


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = sum(retention_trace.seconds(record, program="decode_step",
                                       scope=scope) for scope in SCOPES)
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

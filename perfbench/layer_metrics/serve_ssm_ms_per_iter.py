"""Kernels: device self time under the ``ssm`` named scope (a state-space
mixer: ``ssm_in_proj``, ``ssm_conv``, ``ssm_state_update`` — the decode
kernel over every live stream's page — ``ssm_gate_norm`` and
``ssm_out_proj``) in executions of the ``decode_step`` program, per WHOLE
execution.  ``None`` where the trace holds no such scope (a model without
state-space layers)."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="ssm")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

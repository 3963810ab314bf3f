"""KV cache: device self time under the ``state_copy`` named scope (a
snapshot copied page to page into an admitted stream's own page, or a
stream's page frozen into a snapshot), per WHOLE ``decode_step``
execution of the traced window.  ``None`` where the trace holds no such
scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, scope="state_copy")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

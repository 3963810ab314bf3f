"""Kernels: device self time under the ``attn`` > ``attend_window`` named
scope (the sliding-window layers' paged attend: its kernel and the
once-an-execution plan of the window class's ring) inside executions of the
``decode_step`` program, per WHOLE execution.  Against
``serve_full_attend_ms_per_iter`` it says whether the window layers read
their reach and no more.  ``None`` where the trace holds no such scope."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step",
                                   scope="attend_window")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

"""Kernels: device self time under the ``hc`` named scope (the residual
maps of a model with several residual streams: ``hc_maps`` — the norm over
all streams, the maps' product, sigmoids, Sinkhorn — ``hc_pre`` and
``hc_post``, inside ``attn`` / ``mlp`` / ``moe``) in executions of the
``decode_step`` program, per WHOLE execution.  ``None`` where the trace
holds no such scope (a model with one residual stream)."""
from perfbench.lib import retention_trace, scope_trace


def read(record):
    execs = scope_trace.decode_executions(record)
    secs = retention_trace.seconds(record, program="decode_step", scope="hc")
    if not execs or not secs:
        return None
    return 1e3 * secs / execs

"""Kernels: share of the ``prefill_step`` program's device self time under
the ``attn`` > ``la_chunk`` named scope (the Lightning layers' chunked form
in plain ``jax.numpy``: the page's read, the sub-chunks' decay matrices and
products, the page's and the snapshot's write), in percent: what a prefill
kernel could win.  ``None`` where the trace holds no such scope."""
from perfbench.lib import retention_trace


def read(record):
    total = retention_trace.seconds(record, program="prefill_step")
    chunk = retention_trace.seconds(record, program="prefill_step",
                                    scope="la_chunk")
    if not total or not chunk:
        return None
    return 100.0 * chunk / total

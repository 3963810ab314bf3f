"""Kernels: share of the ``prefill_step`` program's device self time under
the ``ssm`` > ``ssm_chunk_scan`` named scope (the chunked state-space scan
in plain ``jax.numpy``: the page's read, the sub-chunks' decay matrices and
products, the page's and the snapshot's write), in percent: what a prefill
kernel could win.  ``None`` where the trace holds no such scope."""
from perfbench.lib import retention_trace


def read(record):
    total = retention_trace.seconds(record, program="prefill_step")
    scan = retention_trace.seconds(record, program="prefill_step",
                                   scope="ssm_chunk_scan")
    if not total or not scan:
        return None
    return 100.0 * scan / total

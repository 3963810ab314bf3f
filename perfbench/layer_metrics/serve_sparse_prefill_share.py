"""Kernels: share of the ``prefill_step`` program's device self time under
the sparse layers' own scopes (``ck_write``, ``select``, ``attend_sparse``:
the pooled keys written, the selection of every row of the chunk and the
attend with each (row, K/V head) a stream of the kernel), in percent.
``None`` where the trace holds no such scope."""
from perfbench.lib import retention_trace

SCOPES = ("ck_write", "select", "attend_sparse")


def read(record):
    total = retention_trace.seconds(record, program="prefill_step")
    own = sum(retention_trace.seconds(record, program="prefill_step",
                                      scope=name) for name in SCOPES)
    if not total or not own:
        return None
    return 100.0 * own / total

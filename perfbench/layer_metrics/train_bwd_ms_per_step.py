"""Training engine: device self time per traced step of the backward pass:
the operations under ``fwd_bwd`` marked ``transpose(``, the recomputed
forward (``rematted_computation``) included; the ``program_trace`` line
prints the recompute apart.
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.train_metric(record, "bwd")

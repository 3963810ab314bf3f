"""Training engine: device self time per traced step of the forward pass:
the operations under the ``fwd_bwd`` named scope that JAX marks neither
``transpose(`` (backward) nor ``rematted_computation`` (recomputed).
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.train_metric(record, "fwd")

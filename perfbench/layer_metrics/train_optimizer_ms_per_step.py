"""Training engine: device self time per traced step under the ``optimizer``
named scope: norm, clip, the fused kernel, and the assembly of its flat
buffers.
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.train_metric(record, "optimizer")

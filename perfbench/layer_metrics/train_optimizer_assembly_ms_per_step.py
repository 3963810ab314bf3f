"""Kernels: device self time per traced step under ``optimizer/flatten`` and
``optimizer/unflatten``: the data movement that assembles the fused
optimizer's flat buffers and takes them apart again, around a kernel
that ``fused_adam_roofline`` sees alone.
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.train_metric(record, "assembly")

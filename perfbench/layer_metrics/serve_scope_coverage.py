"""Device: share of device self time over the traced window that carries any
of the program's named scopes; compiler-inserted copies of the pool may
carry none (the ``program_trace`` line names the largest unscoped
operations).
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.serve_metric(record, "coverage")

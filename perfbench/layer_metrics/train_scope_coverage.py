"""Device: share of device self time in the traced steps that carries any of
the program's named scopes: what the scope metrics can see.  The
``program_trace`` line names the largest unscoped operations.
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.train_metric(record, "coverage")

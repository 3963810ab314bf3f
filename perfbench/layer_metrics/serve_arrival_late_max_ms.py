"""Serving engine: the largest ``late_ms`` on the ``admit`` spans of the traced
window: how late the scheduler polled for an arrival that was due (a
maximum over some 16 arrivals, not a percentile).
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.serve_metric(record, "arrival_late_max_ms")

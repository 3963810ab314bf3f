"""Serving engine: time inside the engine's ``prefill`` spans over the traced
window: the share of the time every active slot stands still for
admissions.
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.serve_metric(record, "prefill_stall_share")

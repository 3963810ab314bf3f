"""Serving engine: host time per ``decode`` span that is not the wait for the
device in ``decode_fetch``: ``decode_tables`` + ``decode_dispatch`` +
``decode_advance`` + the ``emit`` and ``admit`` spans that follow.
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.serve_metric(record, "host_ms_per_iter")

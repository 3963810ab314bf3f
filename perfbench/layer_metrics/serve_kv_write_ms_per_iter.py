"""KV cache: device self time under the ``kv_write`` named scope inside
executions of the ``decode_step`` program, per WHOLE execution (one that
the window's edge cut counts as the part of a whole one that was traced).
``None`` where the trace holds no span or scope of the program's."""
from perfbench.lib import program_trace


def read(record):
    return program_trace.serve_metric(record, "kv_write_ms_per_iter")

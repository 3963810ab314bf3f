"""Device: ``serve_scope_coverage``'s twin for a program whose scopes
``program_trace.SCOPES`` does not all list (the expert layer's ``moe`` >
..., and ``attend_window`` / ``attend_full`` under ``attn``): share of device
self time over the traced window that carries any named scope of the
program, by ``lib/scope_trace.py``'s reading.  ``None`` where nothing is
scoped, or for a model without classes of cache layers."""
from perfbench.lib import scope_trace


def read(record):
    total = scope_trace.seconds(record)
    scoped = scope_trace.seconds(record, scope="*")
    if not total or not scoped or not (record.get("afmoe") or {}):
        return None
    return 100.0 * scoped / total

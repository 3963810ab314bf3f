"""Device: ``serve_scope_coverage``'s twin for a program whose scopes
``program_trace.SCOPES`` does not all list: share of device self time over
the traced window that carries any named scope of the program (the expert
layer's ``moe`` > ... among them).  ``None`` where nothing is scoped."""
from perfbench.lib import scope_trace


def read(record):
    total = scope_trace.seconds(record)
    scoped = scope_trace.seconds(record, scope="*")
    if not total or not scoped or not (record.get("latent") or {}):
        return None
    return 100.0 * scoped / total

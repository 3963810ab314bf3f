"""Device: ``serve_scope_coverage``'s twin for the ``minicpm_sala`` programs
(both mixers live under ``attn``; ``select``, ``ck_write``, ``la_*`` are in
neither ``program_trace.SCOPES`` nor ``scope_trace.SCOPES``): share of device
self time over the traced window under one of the program's OUTERMOST named
scopes, by ``lib/retention_trace.py``'s any-name reading.  The outermost
scopes do not nest in one another, so their seconds add.  ``None`` where
nothing is scoped, or for another family."""
from perfbench.lib import retention_trace

OUTERMOST = ("embed", "attn", "mlp", "lm_head", "sample", "state_copy",
             "cow_copy")


def read(record):
    total = retention_trace.seconds(record)
    scoped = sum(retention_trace.seconds(record, scope=name)
                 for name in OUTERMOST)
    if not total or not scoped or not (record.get("sala") or {}):
        return None
    return 100.0 * scoped / total

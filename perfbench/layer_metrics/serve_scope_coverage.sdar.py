"""Device: ``serve_scope_coverage``'s twin for the ``sdar_moe`` block step
(``moe`` and ``unmask`` are in neither ``program_trace.SCOPES`` nor
``scope_trace.SCOPES``): share of ``decode_step``'s device self time over
the traced window under one of the program's OUTERMOST named scopes, by
``lib/retention_trace.py``'s any-name reading.  The outermost scopes do not
nest in one another, so their seconds add.  ``None`` where nothing is
scoped, or for another family."""
from perfbench.lib import retention_trace

OUTERMOST = ("embed", "attn", "moe", "lm_head", "unmask")


def read(record):
    total = retention_trace.seconds(record, program="decode_step")
    scoped = sum(retention_trace.seconds(record, program="decode_step",
                                         scope=name) for name in OUTERMOST)
    if not total or not scoped or not (record.get("sdar") or {}):
        return None
    return 100.0 * scoped / total

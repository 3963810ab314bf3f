"""Device: ``serve_scope_coverage``'s twin for a program of the
``kimi_linear`` family (``attn`` > ``kda_*``, which neither
``program_trace.SCOPES`` nor ``scope_trace.SCOPES`` lists): share of device
self time over the traced window under one of the program's OUTERMOST named
scopes, by ``lib/retention_trace.py``'s any-name reading (an instruction
counts where the scope is anywhere on its path).  The outermost scopes do
not nest in one another, so their seconds add.  What is left is the
compiler's own (copies, reshapes, the operands' slices) and the small
programs beside the two steps.  ``None`` where nothing is scoped, or for a
model without KDA layers."""
from perfbench.lib import retention_trace

OUTERMOST = ("embed", "attn", "mlp", "moe", "lm_head", "sample",
             "state_copy", "cow_copy")


def read(record):
    total = retention_trace.seconds(record)
    scoped = sum(retention_trace.seconds(record, scope=name)
                 for name in OUTERMOST)
    if not total or not scoped or not (record.get("kda") or {}):
        return None
    return 100.0 * scoped / total

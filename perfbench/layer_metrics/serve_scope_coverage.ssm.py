"""Device: ``serve_scope_coverage``'s twin for a program with a state-space
mixer (``ssm`` > ..., which neither ``program_trace.SCOPES`` nor
``scope_trace.SCOPES`` lists): share of device self time over the traced
window under one of the program's OUTERMOST named scopes, by
``lib/retention_trace.py``'s any-name reading (an instruction counts where
the scope is anywhere on its path: inside a ``while`` body too).  The
outermost scopes do not nest in one another, so their seconds add.  What is
left is the compiler's own (copies, reshapes, the operands' slices) and the
small programs beside the two steps.  ``None`` where nothing is scoped, or
for a model without state-space layers."""
from perfbench.lib import retention_trace

OUTERMOST = ("embed", "attn", "ssm", "mlp", "lm_head", "sample",
             "state_copy", "cow_copy")


def read(record):
    total = retention_trace.seconds(record)
    scoped = sum(retention_trace.seconds(record, scope=name)
                 for name in OUTERMOST)
    if not total or not scoped or not (record.get("ssm") or {}):
        return None
    return 100.0 * scoped / total

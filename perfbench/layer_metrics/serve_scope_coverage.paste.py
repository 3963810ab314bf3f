"""Device: ``serve_scope_coverage``'s twin for the ``smallthinker``
program (``moe`` > ... in EVERY layer and ``attend_window`` / ``attend_full``
under ``attn``, which ``program_trace.SCOPES`` does not list): share of the
device self time of ``decode_step`` and ``prefill_step`` over the traced
window that lies under one of the program's OUTERMOST named scopes
(``lib/smallthinker_costs.OUTERMOST``: they do not nest in one another, so
their seconds add), by ``lib/retention_trace.py``'s any-name reading.  What
is left is the compiler's own (copies, reshapes, the operands' slices).
``None`` where nothing is scoped, or for another family's record."""
from perfbench.lib import retention_trace, smallthinker_costs as costs


def read(record):
    if not (record.get("smallthinker") or {}):
        return None
    total = scoped = 0.0
    for program in costs.PROGRAMS:
        total += retention_trace.seconds(record, program=program)
        scoped += sum(retention_trace.seconds(record, program=program,
                                              scope=name)
                      for name in costs.OUTERMOST)
    if not total or not scoped:
        return None
    return 100.0 * scoped / total

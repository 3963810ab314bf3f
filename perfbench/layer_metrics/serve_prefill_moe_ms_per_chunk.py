"""MoE: device self time under the ``moe`` named scope (router, dispatch,
the ReLU-gated grouped product over every expert, combine) inside
``prefill_step``, per execution.  ``None`` where the trace holds no such
scope."""
from perfbench.lib import smallthinker_costs


def read(record):
    return smallthinker_costs.ms_per_execution(record, "prefill_step", "moe")

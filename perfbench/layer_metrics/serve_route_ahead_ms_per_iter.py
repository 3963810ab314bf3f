"""MoE: device self time under ``moe`` > ``router`` and ``moe`` >
``dispatch`` (the choice from the block's INPUT, the counting sort, the
tiles and the rows' gather: what a block's routing costs, and what may run
beside its attention since nothing of it reads the attention's output)
inside ``decode_step``, per execution.  ``None`` where the trace holds
neither scope."""
from perfbench.lib import smallthinker_costs


def read(record):
    parts = [smallthinker_costs.ms_per_execution(record, "decode_step", s)
             for s in ("router", "dispatch")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)

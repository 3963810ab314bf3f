"""Kernels: device self time under ``attn`` > ``attend_full`` (the
position-free full layers' paged attend of a chunk over the prompt's whole
context so far) inside ``prefill_step``, per execution.  ``None`` where the
trace holds no such scope."""
from perfbench.lib import smallthinker_costs


def read(record):
    return smallthinker_costs.ms_per_execution(record, "prefill_step",
                                               "attend_full")

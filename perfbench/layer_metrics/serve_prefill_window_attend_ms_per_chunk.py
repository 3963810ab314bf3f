"""Kernels: device self time under ``attn`` > ``attend_window`` (the
sliding-window layers' paged attend of a chunk, in runs of 64 rows that
each walk their own reach, and the once-an-execution plan of the ring)
inside ``prefill_step``, per execution.  ``None`` where the trace holds no
such scope."""
from perfbench.lib import smallthinker_costs


def read(record):
    return smallthinker_costs.ms_per_execution(record, "prefill_step",
                                               "attend_window")

"""Serving engine: device self time of one ``prefill_step`` execution (a
chunk program of up to ``prefill_chunk`` rows of ONE prompt through every
layer: both cache classes' writes and attends, the experts, and for the
chunk that ends a prompt the head), mean over the executions the traced
window holds.  ``None`` where the trace holds no such program."""
from perfbench.lib import smallthinker_costs


def read(record):
    return smallthinker_costs.ms_per_execution(record, "prefill_step")

"""Serving engine: context tokens a LIVE stream holds in a decode iteration,
the mean over the traced window: the ``decode`` spans' ``context_tokens``
(summed over the iteration's streams) over their ``active``.  It is what the
attend's bytes are reckoned from: a change that moves it changed the traffic
served (other sessions admitted, other lengths), not the speed of anything.
``None`` where no traced span carries both."""
from perfbench.lib import scope_trace


def read(record):
    tokens, n = scope_trace.span_arg_sum(record, "decode", "context_tokens")
    active, m = scope_trace.span_arg_sum(record, "decode", "active")
    if not n or not m or not active:
        return None
    return tokens / active

"""Training engine: ms the host spends inside ``engine.train_batch`` a
step, over the traced window: the sum of the program's ``train_batch``
spans' ``host_ms`` (the call's row of the training timeline: ``data_ms``
+ ``dispatch_ms`` + ``log_ms``, from the clock reads that bracket the
three child spans) over the spans that carry it.  The runner wraps each
call in an annotation of its own that is also named ``train_batch`` and
carries no args: ``span_arg_sum`` counts only the spans with the arg, so
the divisor is the program's.  Hidden under the queued steps in the
cells; a loop that fetches each loss pays it every step.  0.0 is a
reading; ``None`` only on a program whose spans carry no such arg."""
from perfbench.lib import scope_trace


def read(record):
    host_ms, spans = scope_trace.span_arg_sum(record, "train_batch",
                                              "host_ms")
    return host_ms / spans if spans else None

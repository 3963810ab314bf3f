"""Training engine: ms of ``engine.train_batch``'s ``data_prep`` a step,
over the traced window: the sum of the program's ``train_batch`` spans'
``data_ms`` (the call's row of the training timeline: entry to the end of
the ``data_prep`` span — the iterator pull, the micro-batch layout and the
``device_put``) over the spans that carry it; the runner's own arg-less
``train_batch`` wrapper is not counted.  The part of the host's pass that
grows with a real loader and with dp.  0.0 is a reading; ``None`` only on
a program whose spans carry no such arg."""
from perfbench.lib import scope_trace


def read(record):
    data_ms, spans = scope_trace.span_arg_sum(record, "train_batch",
                                              "data_ms")
    return data_ms / spans if spans else None

"""Serving engine: median inter-token interval over every (stream,
consecutive emission) of the traced window: the ``emit`` spans' ``gap_ms``,
each counted once per stream that waited it (``continuing``).  ``None`` on a
program whose ``emit`` spans carry no such args."""
from perfbench.lib import serve_timeline


def read(record):
    return serve_timeline.metric(record, "itl_p50_ms")

"""Serving engine: 99th percentile of the inter-token interval over every
(stream, consecutive emission) of the traced window (``emit``: ``gap_ms``
weighted by ``continuing``): what a stream's user feels when other requests'
admissions stand in its way.  ``None`` without the args."""
from perfbench.lib import serve_timeline


def read(record):
    return serve_timeline.metric(record, "itl_p99_ms")

"""Serving engine: percent of all streams' inter-token time in the traced
window spent in OTHER requests' prefill and copies (``emit``: ``stall_ms`` /
``gap_ms``, weighted by ``continuing``).  ``None`` without the args."""
from perfbench.lib import serve_timeline


def read(record):
    return serve_timeline.metric(record, "itl_stall_share")

"""Serving engine: median time from the start of a ``decode_dispatch`` span
to the first device operation of the execution it launched, over the
dispatches that found the device idle.  ``None`` without a device line or the
spans."""
from perfbench.lib import serve_timeline


def read(record):
    return serve_timeline.metric(record, "idle_launch_ms_per_iter")

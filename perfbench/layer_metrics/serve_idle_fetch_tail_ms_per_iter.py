"""Serving engine: median time from the last device operation of a
``decode_step`` execution to the end of the ``decode_fetch`` span that waited
for it: the copy back and the thread's wake-up, device idle, inside a span
``serve_host_ms_per_iter`` leaves out.  ``None`` without a device line or the
spans."""
from perfbench.lib import serve_timeline


def read(record):
    return serve_timeline.metric(record, "idle_fetch_tail_ms_per_iter")

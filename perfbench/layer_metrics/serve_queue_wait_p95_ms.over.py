"""Serving engine, above the knee: 95th percentile, over every request due
inside the window, of ``Request.queue_wait_s`` (due -> slot acquired); a
request that never got a slot counts with the time it had waited when the
window was cut."""


def read(record):
    return (record.get("summary") or {}).get("queue_wait_p95_ms")

"""Serving engine, above the knee: 95th percentile, over every request due
inside the window, of first token minus the time the request was due
(``Request.ttft_s``); a request that never got a slot counts with the
time it had waited when the window was cut.  The queue grows all through
such a run, so this tail swings with the smallest change: it stands
beside the capacity, not under a bound."""


def read(record):
    return (record.get("summary") or {}).get("ttft_p95_ms")

"""Serving engine, above the knee: 95th percentile of the mean gap between
output tokens after the first (``Request.tpot_s``), over the admitted
requests that emitted two tokens or more inside the window (a request
without a slot has no such gap)."""


def read(record):
    return (record.get("summary") or {}).get("tpot_p95_ms")

"""Serving engine: median host-clock time of one decode iteration over
all active slots (``ServingAggregator.snapshot()["decode_step_ms"]``)."""


def read(record):
    step = (record.get("snapshot") or {}).get("decode_step_ms")
    return step["p50"] if step and step.get("n") else None

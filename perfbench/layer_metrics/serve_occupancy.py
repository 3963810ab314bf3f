"""Serving engine: active slots per decode iteration over ``max_slots``
(``snapshot()["occupancy_mean"]``), in percent.  Above the knee every
slot should be taken: what is missing is admission that lags behind
completion."""


def read(record):
    snap = record.get("snapshot") or {}
    if not snap.get("iterations"):
        return None
    return 100.0 * snap["occupancy_mean"]

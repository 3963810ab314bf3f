"""KV cache: share of admitted prompt tokens served from blocks already
resident (``snapshot()["prefix"]["hit_rate"]``), in percent."""


def read(record):
    prefix = (record.get("snapshot") or {}).get("prefix")
    return None if not prefix else 100.0 * prefix["hit_rate"]

"""MoE: the most rows a held expert got in a layer over the mean rows a
held expert got, both as running means over the window's executions
(``snapshot()["model_counters"]``: ``moe_held_max`` / ``moe_held_mean``).
1 is an even load; the grouped product's time follows the largest."""


def read(record):
    c = (record.get("snapshot") or {}).get("model_counters") or {}
    if not c.get("moe_held_mean"):
        return None
    return c["moe_held_max"] / c["moe_held_mean"]

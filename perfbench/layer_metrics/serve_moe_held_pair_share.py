"""MoE: routed (token, expert) pairs that landed on the held experts, of
all pairs the live rows routed, in percent
(``snapshot()["model_counters"]["moe_held_pair_share"]``).  16 of 256
experts under an unbiased router: 6.25."""


def read(record):
    c = (record.get("snapshot") or {}).get("model_counters") or {}
    if "moe_held_pair_share" not in c:
        return None
    return 100.0 * c["moe_held_pair_share"]

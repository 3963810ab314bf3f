"""kind: blockgen -- chat and reasoning requests with a FIXED generation
budget (a block-diffusion endpoint is asked for ``gen_length`` tokens and a
step count; nothing ends a reply early) under an overload with a standing
backlog.

A mix is ``lib/reason_traffic.requests``'s parameters (``backlog`` requests
due at t = 0, the rest open loop over ``[0, seconds)`` at ``rate_rps``;
log-normal prompts, shared system prompts) with ``gen_length`` in place of
a distribution of reply lengths: ``values`` and their ``shares``.  As in
``lib/traffic.py`` nothing is sampled: the budgets are the shares' own
multiset by largest remainder, dealt in the balanced order; the seed deals
the order and draws the token ids, which lie below ``ids`` (the
configuration's mask token: the traffic never draws it).
"""
import numpy as np

from perfbench.lib import reason_traffic, traffic


def budgets(n: int, values, shares) -> np.ndarray:
    """``n`` budgets holding each of ``values`` by its share (largest
    remainder), ascending."""
    exact = n * np.asarray(shares, float) / float(np.sum(shares))
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(values, int), counts)


def requests(spec: dict, seed: int, seconds: float, ids: int) -> list:
    """Dicts ``rid, arrival_s, prompt, max_new_tokens, shared`` by
    arrival: ``spec["backlog"]`` of them at 0, then the open loop."""
    g = spec["gen_length"]
    longest = spec["prompt_len"]["max"] + max(g["values"])
    items = reason_traffic.requests(
        dict(spec, max_total=2 * longest,
             output_len={"median": 1, "sigma": 0.0, "min": 1, "max": 1}),
        seed, seconds, ids)
    gen = traffic._balanced_order(
        budgets(len(items), g["values"], g["shares"]),
        np.random.default_rng([seed, 5]))
    for r, n in zip(items, gen):
        r["max_new_tokens"] = int(n)
    return items

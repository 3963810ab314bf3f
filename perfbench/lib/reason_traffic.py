"""kind: reason -- short prompts answered with long replies (reasoning and
agent endpoints: a task or a tool result of a few hundred tokens, then one
to four thousand tokens of chain of thought) under an overload with a
STANDING BACKLOG.

A mix is ``lib/traffic.serve_requests``'s parameters plus ``backlog``: that
many requests are due at t = 0 (with replies of thousands of tokens an
empty server would take a third of the window to fill its slots from an
open loop alone, and the cell would read its generator); the rest arrive
open loop over ``[0, seconds)`` at ``rate_rps``.  As in ``lib/traffic.py``
nothing is sampled: ONE stratified multiset of ``backlog + rate_rps *
seconds`` lengths and shared-prompt choices in the balanced order (every
eight consecutive requests hold one of each octile, backlog and open loop
alike), the open loop's gaps the stratified quantiles of a Poisson
process's exponential; the seed deals the order and draws the token ids.
"""
import numpy as np

from perfbench.lib import traffic


def requests(spec: dict, seed: int, seconds: float, vocab_size: int) -> list:
    """Dicts ``rid, arrival_s, prompt, max_new_tokens, shared`` by
    arrival: ``spec["backlog"]`` of them at 0, then the open loop."""
    rate, backlog = float(spec["rate_rps"]), int(spec["backlog"])
    n_open = max(1, int(round(rate * seconds)))
    items = traffic.serve_requests(
        dict(spec, rate_rps=(backlog + n_open) / seconds), seed, seconds,
        vocab_size)
    assert len(items) == backlog + n_open, (len(items), backlog, n_open)
    gaps = traffic._balanced_order(traffic.exponential_gaps(n_open, rate),
                                   np.random.default_rng([seed, 4]))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals *= min(1.0, seconds * (n_open - 1) / n_open / gaps.sum())
    for i, r in enumerate(items):
        r["arrival_s"] = 0.0 if i < backlog else float(arrivals[i - backlog])
    return items

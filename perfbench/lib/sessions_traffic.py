"""kind: sessions -- conversations that grow turn by turn, every turn
re-sending the whole history (chat assistants, tool-calling agents;
``workloads.md``: "multi-turn sessions ... high prefix reuse").

A mix is a data file of this generator's parameters.  As in
``lib/traffic.py`` and ``lib/docqa_traffic.py`` nothing is sampled: the
sessions' starting histories are the stratified quantiles of a log-uniform,
message and reply lengths those of log-normals, gaps those of a Poisson
process's exponential; the seed only deals them in another order
(``traffic._balanced_order``) and draws the token ids.  Every request is
the NEXT TURN of a session, and the sessions are dealt in balanced rounds:
every ``count`` consecutive requests hold each session once, in an order
the seed shuffles, so a session's turns are a whole round apart.  Turn k's
prompt = the history + every earlier turn's (message + reply) + message k,
where an earlier reply is the generator's OWN seeded tokens of that turn's
offered reply length: a fixed sequence, so no turn needs anything an
earlier one emitted and the loop stays open.
"""
import numpy as np

from perfbench.lib import traffic
from perfbench.lib.docqa_traffic import document_lengths


def histories(spec: dict, seed: int, vocab_size: int) -> list:
    """The sessions' histories before the window, by rank (rank 0 the
    shortest; the same lengths for every seed)."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab_size, size=int(n), dtype=np.int32)
            for n in document_lengths({"documents": spec["sessions"]})]


def requests(spec: dict, seed: int, seconds: float, vocab_size: int,
             hist: list) -> list:
    """Open-loop requests over ``[0, seconds)``: dicts ``rid, arrival_s,
    prompt, max_new_tokens, shared`` (the session's rank), ``turn``, by
    arrival."""
    rate = float(spec["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    S = len(hist)
    rng = np.random.default_rng([seed, 2])
    m, o = spec["message_len"], spec["output_len"]
    m_len = traffic._balanced_order(traffic.lognormal_lengths(
        n, m["median"], m["sigma"], m["min"], m["max"]), rng)
    o_len = traffic._balanced_order(traffic.lognormal_lengths(
        n, o["median"], o["sigma"], o["min"], o["max"]), rng)
    gaps = traffic._balanced_order(traffic.exponential_gaps(n, rate), rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals *= min(1.0, seconds * (n - 1) / n / gaps.sum())
    order = np.concatenate([rng.permutation(S)
                            for _ in range(-(-n // S))])[:n]
    said = [[h] for h in hist]          # a session's tokens so far, in parts
    out = []
    for i in range(n):
        s = int(order[i])
        said[s].append(rng.integers(0, vocab_size, size=int(m_len[i]),
                                    dtype=np.int32))
        prompt = np.concatenate(said[s])
        new = int(min(o_len[i], spec["max_total"] - len(prompt)))
        if new < 1:
            raise ValueError(
                f"session {s} has outgrown max_total={spec['max_total']} at "
                f"turn {i // S}: {len(prompt)} prompt tokens")
        out.append({"rid": i, "arrival_s": float(arrivals[i]),
                    "prompt": prompt, "shared": s, "turn": i // S,
                    "max_new_tokens": new})
        said[s].append(rng.integers(0, vocab_size, size=new, dtype=np.int32))
    return out

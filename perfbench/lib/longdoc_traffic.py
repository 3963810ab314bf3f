"""kind: longdoc -- short questions over a few VERY long documents that stay
cached (long-document and code-base question answering on a long-context
hybrid model: a document is pasted once and asked many short questions), from
a STANDING BACKLOG (``workloads.md``: "32k to 128k of context ... sparse
selection inside paged attention"; "prompts of 16k to 128k, each asked 3 to 5
times, short answers").

A mix is a data file of this generator's parameters.  As in ``lib/traffic.py``
nothing is sampled: document lengths are the stratified quantiles of a
log-uniform (``docqa_traffic.documents``), question and reply lengths those
of log-normals, gaps those of a Poisson process's exponential, document
choices a Zipf's largest-remainder counts; ``backlog`` requests are due at t =
0, the rest open loop over ``[0, seconds)`` at ``rate_rps``; the seed only
deals the order (``traffic._balanced_order``) and draws the token ids.  A
request's prompt is a concatenation of its document and its question.
"""
import numpy as np

from perfbench.lib import traffic
from perfbench.lib.docqa_traffic import documents  # noqa: F401


def requests(spec: dict, seed: int, seconds: float, vocab_size: int,
             docs: list) -> list:
    """Dicts ``rid, arrival_s, prompt, max_new_tokens, shared`` (the
    document's rank) by arrival: ``spec["backlog"]`` of them at 0, then the
    open loop."""
    rate, backlog = float(spec["rate_rps"]), int(spec["backlog"])
    n_open = max(1, int(round(rate * seconds)))
    n = backlog + n_open
    rng = np.random.default_rng([seed, 2])
    q, o = spec["question_len"], spec["output_len"]
    q_len = traffic._balanced_order(traffic.lognormal_lengths(
        n, q["median"], q["sigma"], q["min"], q["max"]), rng)
    o_len = traffic._balanced_order(traffic.lognormal_lengths(
        n, o["median"], o["sigma"], o["min"], o["max"]), rng)
    gaps = traffic._balanced_order(traffic.exponential_gaps(n_open, rate),
                                   rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals *= min(1.0, seconds * (n_open - 1) / n_open / gaps.sum())
    which = traffic._balanced_order(np.repeat(
        np.arange(len(docs)), traffic._zipf_counts(
            n, len(docs), spec["documents"]["zipf_exponent"])), rng)
    out = []
    for i in range(n):
        doc = docs[int(which[i])]
        prompt = np.concatenate([doc, rng.integers(
            0, vocab_size, size=int(q_len[i]), dtype=np.int32)])
        out.append({"rid": i, "prompt": prompt, "shared": int(which[i]),
                    "arrival_s": 0.0 if i < backlog
                    else float(arrivals[i - backlog]),
                    "max_new_tokens": int(min(
                        o_len[i], spec["max_total"] - len(prompt)))})
    return out

"""kind: docqa -- short questions over a few long, popular documents that
stay cached (document and codebase question-answering, retrieval-augmented
services; Mooncake's traces show this sharing).

A mix is a data file of this generator's parameters.  As in
``lib/traffic.py`` nothing is sampled: document lengths are the n
stratified quantiles of a log-uniform, question and reply lengths those of
log-normals, gaps those of a Poisson process's exponential, document
choices a Zipf's largest-remainder counts, and the seed only deals them in
another order (``traffic._balanced_order``) and draws the token ids.
"""
import numpy as np

from perfbench.lib import traffic


def document_lengths(spec: dict) -> np.ndarray:
    d = spec["documents"]
    u = (np.arange(d["count"]) + 0.5) / d["count"]
    return np.rint(d["min"] * (d["max"] / d["min"]) ** u).astype(int)


def documents(spec: dict, seed: int, vocab_size: int) -> list:
    """The documents' tokens, by rank (rank 0 the shortest AND the most
    asked-about: length and popularity are dealt independently of the
    seed, so every seed holds the same bytes of cache)."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab_size, size=int(n), dtype=np.int32)
            for n in document_lengths(spec)]


def requests(spec: dict, seed: int, seconds: float, vocab_size: int,
             docs: list) -> list:
    """Open-loop requests over ``[0, seconds)``: dicts ``rid, arrival_s,
    prompt (document + question), max_new_tokens, shared`` (the
    document's rank), by arrival."""
    rate = float(spec["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, 2])
    q, o = spec["question_len"], spec["output_len"]
    q_len = traffic._balanced_order(traffic.lognormal_lengths(
        n, q["median"], q["sigma"], q["min"], q["max"]), rng)
    o_len = traffic._balanced_order(traffic.lognormal_lengths(
        n, o["median"], o["sigma"], o["min"], o["max"]), rng)
    gaps = traffic._balanced_order(traffic.exponential_gaps(n, rate), rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals *= min(1.0, seconds * (n - 1) / n / gaps.sum())
    which = traffic._balanced_order(np.repeat(
        np.arange(len(docs)), traffic._zipf_counts(
            n, len(docs), spec["documents"]["zipf_exponent"])), rng)
    out = []
    for i in range(n):
        doc = docs[int(which[i])]
        prompt = np.concatenate([doc, rng.integers(
            0, vocab_size, size=int(q_len[i]), dtype=np.int32)])
        out.append({"rid": i, "arrival_s": float(arrivals[i]),
                    "prompt": prompt, "shared": int(which[i]),
                    "max_new_tokens": int(min(
                        o_len[i], spec["max_total"] - len(prompt)))})
    return out

"""kind: mixed_docqa -- short chat turns and questions over long, popular,
cached documents in ONE queue (a general assistant endpoint: contracts,
codebases and manuals beside plain chat; ``workloads.md``: "short and long
in one queue").

A mix is a data file of this generator's parameters.  As in
``lib/traffic.py`` and ``lib/docqa_traffic.py`` nothing is sampled:
document lengths are the stratified quantiles of a log-uniform, question,
prompt and reply lengths those of log-normals, gaps those of a Poisson
process's exponential, document choices a Zipf's largest-remainder counts;
``long_share`` of the requests are LONG (a document + an unshared
question), the rest SHORT (an unshared prompt and no document), and every
eight consecutive requests hold the same number of each; the seed only
deals them in another order (``traffic._balanced_order``) and draws the
token ids.  The documents are ``docqa_traffic.documents``.
"""
import numpy as np

from perfbench.lib import traffic
from perfbench.lib.docqa_traffic import documents  # noqa: F401


def requests(spec: dict, seed: int, seconds: float, vocab_size: int,
             docs: list) -> list:
    """Open-loop requests over ``[0, seconds)``: dicts ``rid, arrival_s,
    prompt, max_new_tokens, shared`` (the document's rank; -1 for a short
    request), by arrival."""
    rate = float(spec["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    n_long = int(round(float(spec["long_share"]) * n))
    rng = np.random.default_rng([seed, 2])

    def lengths(count, p):
        return traffic._balanced_order(traffic.lognormal_lengths(
            count, p["median"], p["sigma"], p["min"], p["max"]), rng) \
            if count else np.zeros(0, int)
    q_len = lengths(n_long, spec["question_len"])
    p_len = lengths(n - n_long, spec["prompt_len"])
    o_len = lengths(n, spec["output_len"])
    gaps = traffic._balanced_order(traffic.exponential_gaps(n, rate), rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals *= min(1.0, seconds * (n - 1) / n / gaps.sum())
    which = traffic._balanced_order(np.repeat(
        np.arange(len(docs)), traffic._zipf_counts(
            n_long, len(docs), spec["documents"]["zipf_exponent"])), rng) \
        if n_long else np.zeros(0, int)
    is_long = traffic._balanced_order(
        np.arange(n) >= n - n_long, rng).astype(bool)
    out, i_long, i_short = [], 0, 0
    for i in range(n):
        if is_long[i]:
            rank = int(which[i_long])
            prompt = np.concatenate([docs[rank], rng.integers(
                0, vocab_size, size=int(q_len[i_long]), dtype=np.int32)])
            i_long += 1
        else:
            rank = -1
            prompt = rng.integers(0, vocab_size, size=int(p_len[i_short]),
                                  dtype=np.int32)
            i_short += 1
        out.append({"rid": i, "arrival_s": float(arrivals[i]),
                    "prompt": prompt, "shared": rank,
                    "max_new_tokens": int(min(
                        o_len[i], spec["max_total"] - len(prompt)))})
    return out

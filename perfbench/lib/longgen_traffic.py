"""kind: longgen -- a task of about a thousand tokens answered with
thousands (reasoning and agent endpoints of a hybrid linear-attention
model), a few of them questions over a very long, cached document, from a
STANDING BACKLOG (``workloads.md``: "1k in and 8k to 32k out, with a tail of
very long documents", cut to what a window holds).

A mix is a data file of this generator's parameters.  As in
``lib/traffic.py`` nothing is sampled: prompt, question and reply lengths
are the stratified quantiles of log-normals, document lengths those of a
log-uniform (``docqa_traffic.documents``), gaps those of a Poisson process's
exponential, document and system-prompt choices a Zipf's largest-remainder
counts; ``long_share`` of the requests are LONG (a document + an unshared
question), ``shared_prefix.share`` of the others open on one of
``shared_prefix.count`` system prompts, and every eight consecutive requests
hold the same number of each kind; ``backlog`` requests are due at t = 0,
the rest open loop over ``[0, seconds)`` at ``rate_rps``
(``lib/reason_traffic.py``'s shape); the seed only deals the order
(``traffic._balanced_order``) and draws the token ids.
"""
import numpy as np

from perfbench.lib import traffic
from perfbench.lib.docqa_traffic import documents  # noqa: F401


def system_prompts(spec: dict, seed: int, vocab_size: int) -> np.ndarray:
    """int32 ``[count, tokens]``: the shared system prompts, by index."""
    sh = spec["shared_prefix"]
    return np.random.default_rng([seed, 3]).integers(
        0, vocab_size, size=(sh["count"], sh["tokens"]), dtype=np.int32)


def requests(spec: dict, seed: int, seconds: float, vocab_size: int,
             docs: list, system: np.ndarray) -> list:
    """Dicts ``rid, arrival_s, prompt, max_new_tokens, shared`` (the system
    prompt's index, -1 for none), ``doc`` (the document's rank, -1 for
    none) by arrival: ``spec["backlog"]`` of them at 0, then the open
    loop."""
    rate, backlog = float(spec["rate_rps"]), int(spec["backlog"])
    n_open = max(1, int(round(rate * seconds)))
    n = backlog + n_open
    n_long = int(round(float(spec["long_share"]) * n))
    n_plain = n - n_long
    sh = spec["shared_prefix"]
    n_shared = min(int(round(sh["share"] * n_plain)), n_plain // 2) \
        if sh["count"] else 0
    rng = np.random.default_rng([seed, 2])

    def lengths(p, count):
        return traffic.lognormal_lengths(count, p["median"], p["sigma"],
                                         p["min"], p["max"])

    def spread(count, k, exponent):
        """``count`` choices over k ranks by Zipf's largest-remainder
        counts, interleaved so that every stretch holds the mix."""
        ranks = np.repeat(np.arange(k),
                          traffic._zipf_counts(count, k, exponent))
        return ranks[np.argsort(np.arange(count) % max(k, 1), kind="stable")]

    def dealt(*columns):
        """Rows of sorted ``columns`` (the first sorts them) in the balanced
        order: WHICH length goes with which choice is the same for every
        seed, only the order is the seed's."""
        n_rows = len(columns[0])
        order = traffic._balanced_order(np.arange(n_rows), rng) \
            .astype(int) if n_rows else np.zeros(0, int)
        return [np.asarray(c)[order] for c in columns]
    lo, hi = spec["prompt_len"]["min"], spec["prompt_len"]["max"]
    # questions: the i-th shortest over a document dealt by popularity
    q_len, which_doc = dealt(
        lengths(spec["question_len"], n_long),
        spread(n_long, len(docs), spec["documents"]["zipf_exponent"]))
    # prompts: every other one, from the second shortest, behind a system
    # prompt (and then long enough to hold it)
    behind = np.full(n_plain, -1)
    behind[1:2 * n_shared:2] = spread(n_shared, sh["count"],
                                      sh["zipf_exponent"])
    p_len = lengths(spec["prompt_len"], n_plain)
    p_len = np.where(behind >= 0, np.clip(p_len, sh["tokens"] + lo, hi),
                     p_len)
    p_len, behind = dealt(p_len, behind)
    o_len = traffic._balanced_order(lengths(spec["output_len"], n), rng)
    gaps = traffic._balanced_order(traffic.exponential_gaps(n_open, rate),
                                   rng)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals *= min(1.0, seconds * (n_open - 1) / n_open / gaps.sum())
    is_long = traffic._balanced_order(np.arange(n) >= n_plain,
                                      rng).astype(bool)
    out, i_long, i_plain = [], 0, 0
    for i in range(n):
        rank = k = -1
        if is_long[i]:
            rank = int(which_doc[i_long])
            prompt = np.concatenate([docs[rank], rng.integers(
                0, vocab_size, size=int(q_len[i_long]), dtype=np.int32)])
            i_long += 1
        else:
            k = int(behind[i_plain])
            prompt = rng.integers(0, vocab_size, size=int(p_len[i_plain]),
                                  dtype=np.int32)
            if k >= 0:
                prompt[:sh["tokens"]] = system[k]
            i_plain += 1
        out.append({"rid": i, "prompt": prompt, "shared": k, "doc": rank,
                    "arrival_s": 0.0 if i < backlog
                    else float(arrivals[i - backlog]),
                    "max_new_tokens": int(min(
                        o_len[i], spec["max_total"] - len(prompt)))})
    return out
